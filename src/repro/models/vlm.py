"""Llama-3.2-Vision-style VLM text backbone: self-attention decoder with
gated cross-attention layers every ``cross_attn_every``-th layer.

The vision frontend is a STUB per the assignment: ``image_embeds``
(B, n_image_tokens, d_model) arrive precomputed.  Cross-attention layers use
tanh-gated residuals (gates init 0, as in Llama-Vision) and no RoPE on the
image keys.  At decode time the cross KV comes from the prefill cache.

Scan layout: n_layers/cross_attn_every superblocks of
[cross_attn_every - 1 self layers] + [1 cross layer].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from . import layers as L


def _self_layer_init(cfg, key):
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["attn"], s["attn"] = L.attention_init(cfg, k1)
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    return p, s


def _cross_layer_init(cfg, key):
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["xattn"], s["xattn"] = L.attention_init(cfg, k1, cross=True)
    p["gate_attn"], s["gate_attn"] = jnp.zeros((), dt), ()
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    p["gate_mlp"], s["gate_mlp"] = jnp.zeros((), dt), ()
    return p, s


def _stack(init_fn, keys):
    p = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, s1 = init_fn(jax.random.PRNGKey(0))
    s = jax.tree.map(lambda t: (None, *t), s1,
                     is_leaf=lambda t: isinstance(t, tuple))
    return p, s


def init(cfg: ModelConfig, key):
    nb = cfg.n_layers // cfg.cross_attn_every
    per_self = cfg.cross_attn_every - 1
    kemb, ks, kx = jax.random.split(key, 3)
    p, s = {}, {}
    p["tok"], s["tok"] = L.embedding_init(cfg, kemb)
    ps, ss = _stack(lambda k: _self_layer_init(cfg, k),
                    jax.random.split(ks, nb * per_self))
    p["self_layers"] = jax.tree.map(
        lambda a: a.reshape(nb, per_self, *a.shape[1:]), ps)
    s["self_layers"] = jax.tree.map(lambda t: (None, *t), ss,
                                    is_leaf=lambda t: isinstance(t, tuple))
    p["cross_layers"], s["cross_layers"] = _stack(
        lambda k: _cross_layer_init(cfg, k), jax.random.split(kx, nb))
    p["ln_f"], s["ln_f"] = L.norm_init(cfg.d_model, cfg.norm,
                                       jnp.dtype(cfg.param_dtype))
    return p, s


def _self_block(cfg, lp, x, positions, decode_args=None):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    if decode_args is None:
        a = L.attention_apply(cfg, lp["attn"], h, positions=positions)
    else:
        kc, vc, pos = decode_args
        a = L.attention_apply(cfg, lp["attn"], h, mode="decode",
                              positions=positions, k_cache=kc, v_cache=vc,
                              pos=pos)
    x = x + a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), (a.k, a.v)


def _cross_block(cfg, lp, x, positions, img=None, xkv=None):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    if xkv is None:
        a = L.attention_apply(cfg, lp["xattn"], h, positions=positions,
                              kv_src=img)
    else:
        a = L.attention_apply(cfg, lp["xattn"], h, mode="decode",
                              positions=positions, kv_src=h,
                              k_cache=xkv[0], v_cache=xkv[1])
    x = x + jnp.tanh(lp["gate_attn"]).astype(x.dtype) * a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    x = x + jnp.tanh(lp["gate_mlp"]).astype(x.dtype) * L.mlp_apply(
        cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), (a.k, a.v)


def _run(cfg, p, x, positions, img=None, cache=None, pos=None):
    blk_self = jax.checkpoint(
        lambda x, lp, kc=None, vc=None: _self_block(
            cfg, lp, x, positions,
            None if cache is None else (kc, vc, pos)))
    blk_cross = jax.checkpoint(
        lambda x, lp, xk=None, xv=None: _cross_block(
            cfg, lp, x, positions,
            img=img, xkv=None if cache is None else (xk, xv)))

    if cache is None:
        def body(x, bp):
            slp, clp = bp

            def inner(x, lp):
                x, kv = blk_self(x, lp)
                return x, kv
            x, kv_s = jax.lax.scan(inner, x, slp)
            x, kv_x = blk_cross(x, clp)
            return x, (kv_s, kv_x)
        x, (kv_s, kv_x) = jax.lax.scan(
            body, x, (p["self_layers"], p["cross_layers"]))
        return x, {"k_self": kv_s[0], "v_self": kv_s[1],
                   "k_cross": kv_x[0], "v_cross": kv_x[1]}

    def body(x, xs):
        slp, clp, kcs, vcs, kcx, vcx = xs

        def inner(x, inner_xs):
            lp, kc, vc = inner_xs
            x, kv = blk_self(x, lp, kc, vc)
            return x, kv
        x, kv_s = jax.lax.scan(inner, x, (slp, kcs, vcs))
        x, _ = blk_cross(x, clp, kcx, vcx)
        return x, kv_s
    x, kv_s = jax.lax.scan(
        body, x, (p["self_layers"], p["cross_layers"],
                  cache["k_self"], cache["v_self"],
                  cache["k_cross"], cache["v_cross"]))
    return x, {"k_self": kv_s[0], "v_self": kv_s[1],
               "k_cross": cache["k_cross"], "v_cross": cache["v_cross"]}


def forward(cfg: ModelConfig, p, batch):
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    img = batch["image_embeds"].astype(jnp.dtype(cfg.compute_dtype))
    img = constrain(img, "batch", None, None)
    positions = jnp.arange(x.shape[1])
    x, _ = _run(cfg, p, x, positions, img=img)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x)


def prefill(cfg: ModelConfig, p, batch):
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    img = batch["image_embeds"].astype(jnp.dtype(cfg.compute_dtype))
    img = constrain(img, "batch", None, None)
    positions = jnp.arange(x.shape[1])
    x, cache = _run(cfg, p, x, positions, img=img)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x[:, -1:]), cache


def decode(cfg: ModelConfig, p, token, pos, cache):
    # single-step body of Model.decode_fused's k-token scan (donated
    # cache): the static cross-KV leaves are returned unchanged, which
    # under donation is a trivial input->output alias — no copy, and no
    # image re-ingest anywhere in the chunk
    x = L.embed_tokens(cfg, p["tok"], token)
    pos = L.position_vector(pos, x.shape[0])   # per-slot ragged positions
    positions = pos[:, None]
    x, new_cache = _run(cfg, p, x, positions, cache=cache, pos=pos)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x), new_cache


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    nb = cfg.n_layers // cfg.cross_attn_every
    per_self = cfg.cross_attn_every - 1
    self_kv = L.kv_spec(cfg, (nb, per_self), batch, max_seq)
    cross_kv = L.kv_spec(cfg, (nb,), batch, cfg.n_image_tokens)
    return {"k_self": self_kv, "v_self": self_kv,
            "k_cross": cross_kv, "v_cross": cross_kv}


def cache_logical_axes(cfg: ModelConfig):
    return {"k_self": L.kv_logical_axes(2), "v_self": L.kv_logical_axes(2),
            "k_cross": L.kv_logical_axes(1), "v_cross": L.kv_logical_axes(1)}


def cache_seq_axes(cfg: ModelConfig):
    # cross-KV spans the (fixed) image tokens, not the decode position —
    # carried whole in sessions, never trimmed
    return {"k_self": L.kv_seq_axis(2), "v_self": L.kv_seq_axis(2),
            "k_cross": None, "v_cross": None}
