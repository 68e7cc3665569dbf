"""Mixture-of-Experts layer + MoE transformer (granite-moe, qwen3-moe).

Two dispatch implementations, numerically equivalent (tested):

* ``moe_dense`` — GShard-style one-hot einsum dispatch with capacity.  O(T*E*C)
  dispatch memory: correct everywhere, used for small token counts (decode
  steps, smoke tests) and as the correctness oracle.
* ``moe_ep`` — shard_map expert parallelism: tokens sharded over
  (data x model), experts sharded over `model`; sort-based local dispatch,
  ``all_to_all`` to expert owners, expert FFN, reverse ``all_to_all``,
  weighted combine.  This is the production path for train/prefill shapes —
  its collectives (2 all-to-alls over the model axis) are the real EP cost.

Routing: softmax router, top-k, renormalized top-k weights, capacity-factor
token dropping (dropped tokens pass through the residual only).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain, current_rules
from . import layers as L


def moe_init(cfg: ModelConfig, key):
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_expert
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(D)
    p = {
        "router": jax.random.uniform(ks[0], (D, E), dt, -scale, scale),
        "w_gate": jax.random.uniform(ks[1], (E, D, Fe), dt, -scale, scale),
        "w_up": jax.random.uniform(ks[2], (E, D, Fe), dt, -scale, scale),
        "w_down": jax.random.uniform(ks[3], (E, Fe, D), dt,
                                     -1.0 / math.sqrt(Fe), 1.0 / math.sqrt(Fe)),
    }
    s = {
        "router": ("fsdp", "experts"),
        "w_gate": ("experts", "fsdp", None),
        "w_up": ("experts", "fsdp", None),
        "w_down": ("experts", None, "fsdp"),
    }
    return p, s


def _route(cfg: ModelConfig, router_w, x2d):
    """x2d: (T, D) -> (weights (T,k), experts (T,k))."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx


def _expert_ffn(cfg: ModelConfig, p, xe):
    """xe: (E, C, D) slot-major tokens -> (E, C, D)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    xe = xe.astype(cdt)
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(cdt))


# ---------------------------------------------------------------------------
# dense one-hot dispatch (oracle / decode path)
# ---------------------------------------------------------------------------

def moe_dense(cfg: ModelConfig, p, x, min_capacity: int = 0) -> jax.Array:
    B, S, D = x.shape
    T, E, k = B * S, cfg.n_experts, cfg.top_k
    cap = max(1, min_capacity,
              int(math.ceil(T * k * cfg.capacity_factor / E)))
    x2d = x.reshape(T, D)
    vals, idx = _route(cfg, p["router"], x2d)                # (T,k)
    flat_e = idx.reshape(-1)                                 # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                     # position in expert
    pos = jnp.sum(pos * onehot, axis=-1)                     # (T*k,)
    keep = pos < cap
    # dispatch one-hot: (T*k, E, cap)
    disp = (jax.nn.one_hot(flat_e, E, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=x.dtype)[:, None, :cap])
    x_rep = jnp.repeat(x2d, k, axis=0)                       # (T*k, D)
    xe = jnp.einsum("tec,td->ecd", disp, x_rep)              # (E, cap, D)
    ye = _expert_ffn(cfg, p, xe)                             # (E, cap, D)
    y_rep = jnp.einsum("tec,ecd->td", disp, ye)              # (T*k, D)
    w = (vals.reshape(-1) * keep).astype(y_rep.dtype)
    y = (y_rep * w[:, None]).reshape(T, k, D).sum(axis=1)
    return y.reshape(B, S, D).astype(x.dtype)


# ---------------------------------------------------------------------------
# expert-parallel shard_map dispatch (production path)
# ---------------------------------------------------------------------------

def _sorted_positions(flat_e: jax.Array, E: int) -> jax.Array:
    """Rank of each token-copy within its expert, without (T,E) one-hots:
    sort copies by expert, compute run-relative ranks, invert the sort."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    idx = jnp.arange(n)
    is_start = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, idx, 0))
    pos_sorted = idx - run_start
    inv = jnp.argsort(order, stable=True)
    return pos_sorted[inv]


def _local_dispatch(cfg: ModelConfig, x_loc, vals, idx, n_cols: int,
                    cap: int):
    """Build per-destination send buffers on one device.

    x_loc: (N, D); idx/vals: (N, k).  Experts are column-sharded: expert e
    lives on column e // (E/n_cols).  Returns (send (n_cols, E_loc, cap, D),
    slot ids per copy (N*k,), keep mask)."""
    N, D = x_loc.shape
    E, k = cfg.n_experts, cfg.top_k
    e_loc = E // n_cols
    flat_e = idx.reshape(-1)
    pos = _sorted_positions(flat_e, E)
    keep = pos < cap
    # slot id within the (n_cols, e_loc, cap) send buffer
    col = flat_e // e_loc
    le = flat_e % e_loc
    slot = (col * e_loc + le) * cap + pos                    # (N*k,)
    slot = jnp.where(keep, slot, E * cap)                    # overflow slot
    src = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(
        jnp.arange(N * k, dtype=jnp.int32) // k, mode="drop")
    filled = jnp.zeros((E * cap + 1,), bool).at[slot].set(True, mode="drop")
    send = jnp.where(filled[:E * cap, None], x_loc[src[:E * cap]], 0.0)
    return send.reshape(n_cols, e_loc, cap, D), slot, keep


def _moe_ep_local(cfg: ModelConfig, p, x_blk, n_cols: int, axis: str | None):
    """Body run per-device under shard_map (or standalone when axis=None)."""
    b, s, D = x_blk.shape
    N = b * s
    E, k = cfg.n_experts, cfg.top_k
    e_loc = E // n_cols
    cap = max(1, int(math.ceil(N * k * cfg.capacity_factor / E)))
    x2d = x_blk.reshape(N, D)
    vals, idx = _route(cfg, p["router"], x2d)
    send, slot, keep = _local_dispatch(cfg, x2d, vals, idx, n_cols, cap)
    if axis is not None:
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
    else:
        recv = send                                          # 1 column
    # recv: (n_src, e_loc, cap, D) -> (e_loc, n_src*cap, D)
    n_src = recv.shape[0]
    xe = jnp.moveaxis(recv, 0, 1).reshape(e_loc, n_src * cap, D)
    ye = _expert_ffn(cfg, p, xe)
    ye = jnp.moveaxis(ye.reshape(e_loc, n_src, cap, D), 1, 0)
    if axis is not None:
        back = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
    else:
        back = ye
    flat_back = back.reshape(E * cap, D)
    flat_back = jnp.concatenate(
        [flat_back, jnp.zeros((1, D), flat_back.dtype)], axis=0)
    y_copies = flat_back[slot]                               # (N*k, D)
    w = (vals.reshape(-1) * keep).astype(y_copies.dtype)
    y = (y_copies * w[:, None]).reshape(N, k, D).sum(axis=1)
    return y.reshape(b, s, D).astype(x_blk.dtype)


def moe_ep(cfg: ModelConfig, p, x) -> jax.Array:
    """Expert-parallel MoE.  Uses shard_map over (batch-axes, model) when
    sharding rules are active and shapes divide; falls back to the dense
    oracle otherwise."""
    rules = current_rules()
    B, S, D = x.shape
    if rules is None:
        return _moe_ep_local(cfg, p, x, n_cols=1, axis=None)
    mesh = rules.mesh
    model_ax = "model" if "model" in mesh.shape else None
    batch_axes = tuple(a for a in rules.rules.get("batch", ())
                       if a in mesh.shape)
    n_cols = mesh.shape[model_ax] if model_ax else 1
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if (model_ax is None or cfg.n_experts % n_cols or S % n_cols
            or B % max(n_batch, 1)):
        return moe_dense(cfg, p, x)

    pspec_x = P(batch_axes if batch_axes else None, model_ax, None)
    rep = P(*([None] * 2))
    pspec_p = {
        "router": rep,
        "w_gate": P(model_ax, None, None),
        "w_up": P(model_ax, None, None),
        "w_down": P(model_ax, None, None),
    }

    body = partial(_moe_ep_local, cfg, n_cols=n_cols, axis=model_ax)
    fn = jax.shard_map(lambda pp, xx: body(pp, xx), mesh=mesh,
                       in_specs=(pspec_p, pspec_x), out_specs=pspec_x,
                       check_vma=False)
    return fn(p, x)


def moe_apply(cfg: ModelConfig, p, x, *, decode: bool = False) -> jax.Array:
    # decode steps and tiny token counts use the einsum oracle; full
    # sequences use expert-parallel shard_map dispatch.  Decode runs with
    # no-drop capacity (cap = token count >= worst-case one copy per token
    # per expert): a slot's output then never depends on which other slots
    # share the batch, which is what makes session migration between
    # engines token-identical under greedy decoding.
    if decode:
        return moe_dense(cfg, p, x, min_capacity=x.shape[0] * x.shape[1])
    if x.shape[0] * x.shape[1] <= 4096:
        return moe_dense(cfg, p, x)
    return moe_ep(cfg, p, x)


# ---------------------------------------------------------------------------
# MoE transformer (every `moe_every`-th layer replaces the dense MLP)
# ---------------------------------------------------------------------------

def _layer_init(cfg: ModelConfig, key, moe_layer: bool):
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    p["attn"], s["attn"] = L.attention_init(cfg, k1)
    p["ln2"], s["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dt)
    if moe_layer:
        p["moe"], s["moe"] = moe_init(cfg, k2)
    else:
        p["mlp"], s["mlp"] = L.mlp_init(cfg, k2)
    return p, s


def _stacked_init(cfg: ModelConfig, key, layer_ids):
    """Stack params for a homogeneous set of layers."""
    moe_layer = cfg.is_moe_layer(layer_ids[0])
    keys = jax.random.split(key, len(layer_ids))
    p = jax.vmap(lambda k: _layer_init(cfg, k, moe_layer)[0])(keys)
    _, s1 = _layer_init(cfg, jax.random.PRNGKey(0), moe_layer)
    s = jax.tree.map(lambda t: (None, *t), s1,
                     is_leaf=lambda t: isinstance(t, tuple))
    return p, s


def init(cfg: ModelConfig, key):
    kemb, klay = jax.random.split(key)
    p, s = {}, {}
    p["tok"], s["tok"] = L.embedding_init(cfg, kemb)
    if cfg.moe_every == 1:
        p["layers"], s["layers"] = _stacked_init(
            cfg, klay, list(range(cfg.n_layers)))
    else:
        # alternate dense/moe: scan over super-blocks of `moe_every` layers
        n_blocks = cfg.n_layers // cfg.moe_every
        kd, km = jax.random.split(klay)
        dense_ids = [i for i in range(cfg.n_layers) if not cfg.is_moe_layer(i)]
        moe_ids = [i for i in range(cfg.n_layers) if cfg.is_moe_layer(i)]
        pd, sd = _stacked_init(cfg, kd, dense_ids)
        pm, sm = _stacked_init(cfg, km, moe_ids)
        # reshape leading axis: (n_blocks, per_block, ...)
        per_d = len(dense_ids) // n_blocks
        p["dense_layers"] = jax.tree.map(
            lambda a: a.reshape(n_blocks, per_d, *a.shape[1:]), pd)
        s["dense_layers"] = jax.tree.map(lambda t: (None, *t), sd,
                                         is_leaf=lambda t: isinstance(t, tuple))
        p["moe_layers"] = pm
        s["moe_layers"] = sm
    p["ln_f"], s["ln_f"] = L.norm_init(cfg.d_model, cfg.norm,
                                       jnp.dtype(cfg.param_dtype))
    return p, s


def _block(cfg, lp, x, positions, moe_layer: bool, decode_args=None):
    h = L.apply_norm(lp["ln1"], x, cfg.norm)
    if decode_args is None:
        a = L.attention_apply(cfg, lp["attn"], h, positions=positions)
        kv = (a.k, a.v)
    else:
        kc, vc, pos = decode_args
        a = L.attention_apply(cfg, lp["attn"], h, mode="decode",
                              positions=positions, k_cache=kc, v_cache=vc,
                              pos=pos)
        kv = (a.k, a.v)
    x = x + a.x
    h = L.apply_norm(lp["ln2"], x, cfg.norm)
    if moe_layer:
        x = x + moe_apply(cfg, lp["moe"], h, decode=decode_args is not None)
    else:
        x = x + L.mlp_apply(cfg, lp["mlp"], h)
    return constrain(x, "batch", "seq_sp", None), kv


def _run_layers(cfg, p, x, positions, collect_kv: bool,
                cache=None, pos=None):
    caches = {"k": [], "v": []}
    if cfg.moe_every == 1:
        blk = jax.checkpoint(
            lambda x, lp, kc=None, vc=None: _block(
                cfg, lp, x, positions, True,
                None if cache is None else (kc, vc, pos)))
        if cache is None:
            def body(x, lp):
                x, kv = blk(x, lp)
                return x, kv
            x, (ks, vs) = jax.lax.scan(body, x, p["layers"])
        else:
            def body(x, xs):
                lp, kc, vc = xs
                x, kv = blk(x, lp, kc, vc)
                return x, kv
            x, (ks, vs) = jax.lax.scan(
                body, x, (p["layers"], cache["k"], cache["v"]))
        return x, {"k": ks, "v": vs}
    # super-block scan: per_d dense layers then 1 moe layer per block
    blk_dense = jax.checkpoint(
        lambda x, lp, kc=None, vc=None: _block(
            cfg, lp, x, positions, False,
            None if cache is None else (kc, vc, pos)))
    blk_moe = jax.checkpoint(
        lambda x, lp, kc=None, vc=None: _block(
            cfg, lp, x, positions, True,
            None if cache is None else (kc, vc, pos)))

    if cache is None:
        def body(x, xs):
            dlp, mlp_ = xs

            def inner(x, lp):
                x, kv = blk_dense(x, lp)
                return x, kv
            x, kv_d = jax.lax.scan(inner, x, dlp)
            x, kv_m = blk_moe(x, mlp_)
            return x, (kv_d, kv_m)
        x, (kv_d, kv_m) = jax.lax.scan(body, x, (p["dense_layers"],
                                                 p["moe_layers"]))
        return x, {"k_dense": kv_d[0], "v_dense": kv_d[1],
                   "k_moe": kv_m[0], "v_moe": kv_m[1]}

    def body(x, xs):
        dlp, mlp_, kcd, vcd, kcm, vcm = xs

        def inner(x, inner_xs):
            lp, kc, vc = inner_xs
            x, kv = blk_dense(x, lp, kc, vc)
            return x, kv
        x, kv_d = jax.lax.scan(inner, x, (dlp, kcd, vcd))
        x, kv_m = blk_moe(x, mlp_, kcm, vcm)
        return x, (kv_d, kv_m)
    x, (kv_d, kv_m) = jax.lax.scan(
        body, x, (p["dense_layers"], p["moe_layers"],
                  cache["k_dense"], cache["v_dense"],
                  cache["k_moe"], cache["v_moe"]))
    return x, {"k_dense": kv_d[0], "v_dense": kv_d[1],
               "k_moe": kv_m[0], "v_moe": kv_m[1]}


def forward(cfg: ModelConfig, p, batch) -> jax.Array:
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x, _ = _run_layers(cfg, p, x, positions, collect_kv=False)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x)


def prefill(cfg: ModelConfig, p, batch):
    x = L.embed_tokens(cfg, p["tok"], batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x, cache = _run_layers(cfg, p, x, positions, collect_kv=True)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x[:, -1:]), cache


def decode(cfg: ModelConfig, p, token, pos, cache):
    # single-step body of Model.decode_fused's k-token scan (donated cache):
    # decode-time MoE keeps no-drop capacity, so a chunk's tokens stay
    # batch-composition independent — migration/truncation mid-chunk cannot
    # change any other slot's stream
    x = L.embed_tokens(cfg, p["tok"], token)
    pos = L.position_vector(pos, x.shape[0])   # per-slot ragged positions
    if cfg.moe_every == 1:
        # in-place token-slice cache update (see transformer.decode)
        def body(carry, xs):
            x, kf, vf = carry
            lp, i = xs
            h = L.apply_norm(lp["ln1"], x, cfg.norm)
            out, kf, vf = L.attention_decode_inplace(
                cfg, lp["attn"], h, kf, vf, i, pos)
            x = x + out
            h = L.apply_norm(lp["ln2"], x, cfg.norm)
            x = x + moe_apply(cfg, lp["moe"], h, decode=True)
            return (x, kf, vf), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (p["layers"], jnp.arange(cfg.n_layers)))
        new_cache = {"k": ks, "v": vs}
    else:
        positions = pos[:, None]
        x, new_cache = _run_layers(cfg, p, x, positions, collect_kv=True,
                                   cache=cache, pos=pos)
    x = L.apply_norm(p["ln_f"], x, cfg.norm)
    return L.lm_head(cfg, p["tok"], x), new_cache


def cache_spec(cfg: ModelConfig, batch: int, max_seq: int):
    if cfg.moe_every == 1:
        kv = L.kv_spec(cfg, (cfg.n_layers,), batch, max_seq)
        return {"k": kv, "v": kv}
    nb = cfg.n_layers // cfg.moe_every
    dense = L.kv_spec(cfg, (nb, cfg.moe_every - 1), batch, max_seq)
    moe = L.kv_spec(cfg, (nb,), batch, max_seq)
    return {"k_dense": dense, "v_dense": dense, "k_moe": moe, "v_moe": moe}


def cache_logical_axes(cfg: ModelConfig):
    if cfg.moe_every == 1:
        return {"k": L.kv_logical_axes(1), "v": L.kv_logical_axes(1)}
    return {"k_dense": L.kv_logical_axes(2), "v_dense": L.kv_logical_axes(2),
            "k_moe": L.kv_logical_axes(1), "v_moe": L.kv_logical_axes(1)}


def cache_seq_axes(cfg: ModelConfig):
    if cfg.moe_every == 1:
        return {"k": L.kv_seq_axis(1), "v": L.kv_seq_axis(1)}
    return {"k_dense": L.kv_seq_axis(2), "v_dense": L.kv_seq_axis(2),
            "k_moe": L.kv_seq_axis(1), "v_moe": L.kv_seq_axis(1)}
