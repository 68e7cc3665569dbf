"""Jamba-style hybrid (arXiv:2403.19887): attention:mamba 1:7 interleave with
MoE every 2nd layer (16 of 32 layers for jamba-v0.1-52b).

Superblock layout (scanned over n_layers/attn_every superblocks):
  pos 0: attention + dense MLP
  pos 1,3,5,7: mamba + MoE        (4 per superblock)
  pos 2,4,6:   mamba + dense MLP  (3 per superblock)

Attention layers carry no RoPE (positions come from the SSM layers, as in
Jamba).  State: KV cache for the attention layer + SSM/conv state per mamba
layer, all stacked along the superblock axis for the decode scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from . import layers as L
from . import mamba2 as S
from .moe import moe_apply, moe_init


def _attn_layer_init(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["attn"], s["attn"] = L.attention_init(cfg, k1)
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    return p, s


def _mamba_layer_init(cfg: ModelConfig, key, moe: bool):
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["ssm"], s["ssm"] = S.ssm_layer_init(cfg, k1)
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    if moe:
        p["moe"], s["moe"] = moe_init(cfg, k2)
    else:
        p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    return p, s


def _stack(init_fn, keys):
    p = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, s1 = init_fn(jax.random.PRNGKey(0))
    s = jax.tree.map(lambda t: (None, *t), s1,
                     is_leaf=lambda t: isinstance(t, tuple))
    return p, s


def init(cfg: ModelConfig, key):
    nb = cfg.n_layers // cfg.attn_every
    kemb, ka, km, kd = jax.random.split(key, 4)
    p, s = {}, {}
    p["tok"], s["tok"] = L.embedding_init(cfg, kemb)
    p["attn_layers"], s["attn_layers"] = _stack(
        lambda k: _attn_layer_init(cfg, k), jax.random.split(ka, nb))
    pm, sm = _stack(lambda k: _mamba_layer_init(cfg, k, True),
                    jax.random.split(km, nb * 4))
    p["mamba_moe"] = jax.tree.map(lambda a: a.reshape(nb, 4, *a.shape[1:]), pm)
    s["mamba_moe"] = jax.tree.map(lambda t: (None, *t), sm,
                                  is_leaf=lambda t: isinstance(t, tuple))
    pd, sd = _stack(lambda k: _mamba_layer_init(cfg, k, False),
                    jax.random.split(kd, nb * 3))
    p["mamba_dense"] = jax.tree.map(lambda a: a.reshape(nb, 3, *a.shape[1:]), pd)
    s["mamba_dense"] = jax.tree.map(lambda t: (None, *t), sd,
                                    is_leaf=lambda t: isinstance(t, tuple))
    p["ln_f"], s["ln_f"] = L.norm_init(cfg.d_model, cfg.norm,
                                       jnp.dtype(cfg.param_dtype))
    return p, s


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _attn_block(cfg, lp, x, positions, decode_args=None):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    if decode_args is None:
        a = L.attention_apply(cfg, lp["attn"], h, positions=positions,
                              rope=False)
    else:
        kc, vc, pos = decode_args
        a = L.attention_apply(cfg, lp["attn"], h, mode="decode",
                              positions=positions, k_cache=kc, v_cache=vc,
                              pos=pos, rope=False)
    x = x + a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), (a.k, a.v)


def _mamba_block(cfg, lp, x, moe: bool, state=None, decode=False):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    if decode:
        h0, conv = state
        out, new_state = S.ssm_layer_step(cfg, lp["ssm"], h, h0, conv)
    else:
        out, new_state = S.ssm_layer_full(cfg, lp["ssm"], h,
                                          conv_state=jnp.zeros(()))
    x = x + out
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    if moe:
        x = x + moe_apply(cfg, lp["moe"], h, decode=decode)
    else:
        x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), new_state


def _superblock(cfg, bp, x, positions, state=None, pos=None):
    """One attention layer + interleaved [moe, dense]*3 + final moe mamba."""
    decode = state is not None
    blk_attn = jax.checkpoint(
        lambda x, lp, kc=None, vc=None: _attn_block(
            cfg, lp, x, positions, None if not decode else (kc, vc, pos)))
    blk_moe = jax.checkpoint(
        lambda x, lp, st=None: _mamba_block(cfg, lp, x, True, st, decode))
    blk_dense = jax.checkpoint(
        lambda x, lp, st=None: _mamba_block(cfg, lp, x, False, st, decode))

    take = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    if not decode:
        x, kv = blk_attn(x, bp["attn_layer"])
        # scan the 3 [moe, dense] mamba pairs: a python loop makes XLA
        # co-schedule all pairs' backward recomputes (the 58 GiB/dev hog
        # attributed in EXPERIMENTS.md §Perf); a scan serializes them
        pair_moe = jax.tree.map(lambda a: a[:3], bp["mamba_moe"])
        pair_dense = bp["mamba_dense"]

        def pair_step(x, lps):
            lp_m, lp_d = lps
            x, st_m = blk_moe(x, lp_m)
            x, st_d = blk_dense(x, lp_d)
            return x, (st_m, st_d)

        x, (moe_sts, dense_sts) = jax.lax.scan(
            pair_step, x, (pair_moe, pair_dense))
        x, st_last = blk_moe(x, take(bp["mamba_moe"], 3))
        moe_states = tuple(
            jnp.concatenate([s, sl[None]], axis=0)
            for s, sl in zip(moe_sts, st_last))
        return x, (kv, moe_states, dense_sts)

    kv_c, moe_st, dense_st = state
    x, kv = blk_attn(x, bp["attn_layer"], kv_c[0], kv_c[1])
    moe_states, dense_states = [], []
    for i in range(3):
        x, st_m = blk_moe(x, take(bp["mamba_moe"], i),
                          jax.tree.map(lambda a: a[i], moe_st))
        moe_states.append(st_m)
        x, st_d = blk_dense(x, take(bp["mamba_dense"], i),
                            jax.tree.map(lambda a: a[i], dense_st))
        dense_states.append(st_d)
    x, st_m = blk_moe(x, take(bp["mamba_moe"], 3),
                      jax.tree.map(lambda a: a[3], moe_st))
    moe_states.append(st_m)
    stack = lambda sts: tuple(jnp.stack(z) for z in zip(*sts))
    return x, (kv, stack(moe_states), stack(dense_states))


def _run(cfg, p, x, positions, cache=None, pos=None):
    blocks = {"attn_layer": p["attn_layers"], "mamba_moe": p["mamba_moe"],
              "mamba_dense": p["mamba_dense"]}
    if cache is None:
        def body(x, bp):
            x, st = _superblock(cfg, bp, x, positions)
            return x, st
        x, sts = jax.lax.scan(body, x, blocks)
        return x, sts
    cache_xs = ((cache["k"], cache["v"]),
                (cache["ssm_moe"], cache["conv_moe"]),
                (cache["ssm_dense"], cache["conv_dense"]))

    def body(x, xs):
        bp, st = xs
        x, new_st = _superblock(cfg, bp, x, positions, state=st, pos=pos)
        return x, new_st
    x, sts = jax.lax.scan(body, x, (blocks, cache_xs))
    return x, sts


def _pack_cache(sts):
    (k, v), (ssm_m, conv_m), (ssm_d, conv_d) = sts
    return {"k": k, "v": v, "ssm_moe": ssm_m, "conv_moe": conv_m,
            "ssm_dense": ssm_d, "conv_dense": conv_d}


def forward(cfg: ModelConfig, p, batch):
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x, _ = _run(cfg, p, x, positions)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x)


def prefill(cfg: ModelConfig, p, batch):
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x, sts = _run(cfg, p, x, positions)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x[:, -1:]), _pack_cache(sts)


def decode(cfg: ModelConfig, p, token, pos, cache):
    # single-step body of Model.decode_fused's k-token scan: the hybrid
    # cache (attention KV + per-mamba-layer SSM/conv state) is donated
    # whole — every leaf returned here must keep its input shape/dtype so
    # XLA can alias the buffers
    x = L.embed_tokens(cfg, p["tok"], token)
    pos = L.position_vector(pos, x.shape[0])   # per-slot ragged positions
    positions = pos[:, None]
    x, sts = _run(cfg, p, x, positions, cache=cache, pos=pos)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x), _pack_cache(sts)


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    nb = cfg.n_layers // cfg.attn_every
    nh, hp, ds = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * ds
    cdt = jnp.dtype(cfg.compute_dtype)
    kv = L.kv_spec(cfg, (nb,), batch, max_seq)
    return {
        "k": kv,
        "v": kv,
        "ssm_moe": jax.ShapeDtypeStruct((nb, 4, batch, nh, hp, ds),
                                        jnp.float32),
        "conv_moe": jax.ShapeDtypeStruct(
            (nb, 4, batch, cfg.ssm_conv - 1, conv_dim), cdt),
        "ssm_dense": jax.ShapeDtypeStruct((nb, 3, batch, nh, hp, ds),
                                          jnp.float32),
        "conv_dense": jax.ShapeDtypeStruct(
            (nb, 3, batch, cfg.ssm_conv - 1, conv_dim), cdt),
    }


def cache_logical_axes(cfg: ModelConfig):
    return {
        "k": L.kv_logical_axes(1),
        "v": L.kv_logical_axes(1),
        "ssm_moe": (None, None, "batch", None, None, None),
        "conv_moe": (None, None, "batch", None, "ff"),
        "ssm_dense": (None, None, "batch", None, None, None),
        "conv_dense": (None, None, "batch", None, "ff"),
    }


def cache_seq_axes(cfg: ModelConfig):
    # only the attention KV grows with position; SSM/conv state is O(1)
    return {"k": L.kv_seq_axis(1), "v": L.kv_seq_axis(1), "ssm_moe": None,
            "conv_moe": None, "ssm_dense": None, "conv_dense": None}
