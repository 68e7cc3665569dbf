"""Shared model layers: norms, RoPE, GQA attention (blocked-flash for full
sequences, flash-decode with sharded KV for serving), dense MLP, embeddings.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every init
function returns ``(params, specs)`` where ``specs`` mirrors the params tree
with tuples of *logical* axis names (see repro.distributed.sharding); the
launcher maps them to NamedShardings.

Memory discipline: full-sequence attention is computed with an online-softmax
two-level blocking (lax.map over Q blocks, lax.scan over KV blocks), so the
(S x S) score matrix is never materialized — required for the 32k prefill
cells, and the jnp oracle the Pallas flash kernel is validated against.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from ..kernels.ragged_decode import ragged_decode_attention
from ..kernels.ragged_decode.ref import decode_attend_ref
from ..kernels.ragged_prefill import ragged_prefill_attention

Params = Any   # nested dict pytree
Specs = Any


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, in_axis, out_axis,
               dtype) -> tuple[jax.Array, tuple]:
    scale = 1.0 / math.sqrt(in_dim)
    w = jax.random.uniform(key, (in_dim, out_dim), dtype, -scale, scale)
    return w, (in_axis, out_axis)


def norm_init(d: int, kind: str, dtype) -> tuple[Params, Specs]:
    if kind == "layernorm":
        return ({"scale": jnp.ones((d,), dtype),
                 "bias": jnp.zeros((d,), dtype)},
                {"scale": (None,), "bias": (None,)})
    return {"scale": jnp.ones((d,), dtype)}, {"scale": (None,)}


def apply_norm(p: Params, x: jax.Array, kind: str) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def position_vector(pos, batch: int) -> jax.Array:
    """Normalize a decode position — scalar (shared) or per-slot vector — to
    an int32 ``(batch,)`` vector.  Ragged continuous batching passes one
    position per slot; legacy callers pass a scalar."""
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    if pos.shape[0] == batch:
        return pos
    return jnp.broadcast_to(pos, (batch,))


def rope_frequencies(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# KV cache layout
# ---------------------------------------------------------------------------
# Every attention cache leaf of every family is ``(*lead, B, Hkv, hd, S)``:
# the sequence axis is minor, which is how the TPU stores it (S on the
# lanes, hd on the sublanes: no padding of hd 64 to 128 lanes) and what the
# decode kernel reads and writes in place.  ``lead`` is the family's layer
# stacking.  cache_spec / cache_logical_axes / cache_seq_axes derive from
# these three helpers, and so do session extract/insert and the wire.

KV_AXES = ("batch", None, None, "seq_mp")     # B, Hkv, hd, S


def kv_spec(cfg: ModelConfig, lead: tuple[int, ...], batch: int,
            seq: int) -> jax.ShapeDtypeStruct:
    """One K or V cache leaf: ``(*lead, batch, Hkv, hd, seq)``."""
    return jax.ShapeDtypeStruct((*lead, batch, cfg.n_kv_heads, cfg.hd, seq),
                                jnp.dtype(cfg.compute_dtype))


def kv_logical_axes(n_lead: int) -> tuple:
    """Logical axis names of a K or V leaf with ``n_lead`` stacking axes."""
    return (None,) * n_lead + KV_AXES


def kv_seq_axis(n_lead: int) -> int:
    """The axis of a K or V leaf that grows with the decode position."""
    return n_lead + KV_AXES.index("seq_mp")


def to_kv_layout(x: jax.Array) -> jax.Array:
    """(B, S, Hkv, hd) projections -> the cache layout (B, Hkv, hd, S)."""
    return x.transpose(0, 2, 3, 1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_init(cfg: ModelConfig, key, cross: bool = False
                   ) -> tuple[Params, Specs]:
    D, hd, Hq, Hkv = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["wq"], s["wq"] = dense_init(ks[0], D, Hq * hd, "fsdp", "qkv", dt)
    p["wk"], s["wk"] = dense_init(ks[1], D, Hkv * hd, "fsdp", "qkv", dt)
    p["wv"], s["wv"] = dense_init(ks[2], D, Hkv * hd, "fsdp", "qkv", dt)
    p["wo"], s["wo"] = dense_init(ks[3], Hq * hd, D, "qkv", "fsdp", dt)
    if cfg.qkv_bias:
        p["bq"], s["bq"] = jnp.zeros((Hq * hd,), dt), ("qkv",)
        p["bk"], s["bk"] = jnp.zeros((Hkv * hd,), dt), ("qkv",)
        p["bv"], s["bv"] = jnp.zeros((Hkv * hd,), dt), ("qkv",)
    if cfg.qk_norm:
        p["q_norm"], s["q_norm"] = jnp.ones((hd,), dt), (None,)
        p["k_norm"], s["k_norm"] = jnp.ones((hd,), dt), (None,)
    return p, s


def _qkv(cfg: ModelConfig, p: Params, x: jax.Array, kv_src: jax.Array,
         positions, kv_positions, rope: bool):
    B = x.shape[0]
    hd, Hq, Hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    cdt = jnp.dtype(cfg.compute_dtype)
    q = x @ p["wq"].astype(cdt)
    k = kv_src @ p["wk"].astype(cdt)
    v = kv_src @ p["wv"].astype(cdt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(B, -1, Hq, hd)
    k = k.reshape(B, -1, Hkv, hd)
    v = v.reshape(B, -1, Hkv, hd)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm"])
        k = _rms_head(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _rms_head(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + 1e-6) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def blocked_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                      v: jax.Array, causal: bool,
                      q_offset: int = 0) -> jax.Array:
    """Online-softmax two-level blocked attention (jnp flash oracle).

    q: (B, Sq, Hq, hd); k,v: (B, Skv, Hkv, hd).  Never materializes SxS.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    qb = min(cfg.q_block, Sq)
    kb = min(cfg.kv_block, Skv)
    if (causal and cfg.causal_scheme == "wrapped" and q_offset == 0
            and Sq == Skv):
        kb = qb                     # wrapped pairing needs square tiles
    nq, nk = -(-Sq // qb), -(-Skv // kb)
    pad_q, pad_k = nq * qb - Sq, nk * kb - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # (B, nq, qb, Hkv, rep, hd) / (B, nk, kb, Hkv, hd)
    qr = q.reshape(B, nq, qb, Hkv, rep, hd)
    kr = k.reshape(B, nk, kb, Hkv, hd)
    vr = v.reshape(B, nk, kb, Hkv, hd)
    scale = 1.0 / math.sqrt(hd)
    neg = jnp.float32(-1e30)

    def q_block(args):
        qi, qblk = args                                 # (B, qb, Hkv, rep, hd)
        q_pos = q_offset + qi * qb + jnp.arange(qb)

        @jax.checkpoint
        def kv_step(carry, kv):
            m, l, acc = carry
            ki, kblk, vblk = kv
            k_pos = ki * kb + jnp.arange(kb)
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = k_pos[None, :] <= q_pos[:, None] if causal else (
                jnp.ones((qb, kb), bool))
            mask = mask & (k_pos < Skv)[None, :] & (q_pos < q_offset + Sq)[:, None]
            s = jnp.where(mask[None, None, None], s, neg)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, rep, qb), neg, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, qb), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, qb, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                      # (B, g, r, qb, hd)

    if (causal and cfg.causal_scheme == "wrapped" and q_offset == 0
            and Sq == Skv and nq == nk and nq % 2 == 0 and not pad_q):
        outs = _wrapped_causal(cfg, qr, kr, vr, B, Hkv, rep, qb, kb, nq,
                               hd, scale, Skv)
    else:
        with jax.named_scope("flashattn"):
            outs = jax.lax.map(jax.checkpoint(q_block),
                               (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    # (nq, B, g, r, qb, hd) -> (B, nq*qb, g*r, hd)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, rep, nq * qb, hd)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, nq * qb, Hq * hd)
    return out[:, :Sq].astype(q.dtype)


def _wrapped_causal(cfg, qr, kr, vr, B, Hkv, rep, qb, kb, nq, hd, scale,
                    Skv):
    """Load-balanced causal blocking: q-tile pair (lo=p, hi=nq-1-p) sweeps
    k-tiles 0..nq together — (nq+1) tile-products per pair instead of 2*nq,
    i.e. the triangular flop skip a flash kernel does, in pure jnp.
    Each step computes ONE tile product against whichever pair member still
    needs it."""
    neg = jnp.float32(-1e30)
    krm = jnp.moveaxis(kr, 1, 0)          # (nk, B, kb, g, hd)
    vrm = jnp.moveaxis(vr, 1, 0)

    def pair(p):
        lo, hi = p, nq - 1 - p
        q_lo = qr[:, lo]                   # (B, qb, g, rep, hd)
        q_hi = qr[:, hi]

        @jax.checkpoint
        def step(carry, j):
            m_l, l_l, a_l, m_h, l_h, a_h = carry
            use_lo = j <= lo
            ki = jnp.where(use_lo, j, j - lo - 1)
            kblk = jax.lax.dynamic_index_in_dim(krm, ki, 0, keepdims=False)
            vblk = jax.lax.dynamic_index_in_dim(vrm, ki, 0, keepdims=False)
            qblk = jnp.where(use_lo, q_lo, q_hi)
            q_start = jnp.where(use_lo, lo, hi) * qb
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            qpos = q_start + jnp.arange(qb)[:, None]
            kpos = ki * kb + jnp.arange(kb)[None, :]
            s = jnp.where((kpos <= qpos)[None, None, None], s, neg)
            m_c = jnp.where(use_lo, m_l, m_h)
            l_c = jnp.where(use_lo, l_l, l_h)
            a_c = jnp.where(use_lo, a_l, a_h)
            m_new = jnp.maximum(m_c, s.max(-1))
            pexp = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_c - m_new)
            l_new = l_c * corr + pexp.sum(-1)
            a_new = a_c * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", pexp.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            m_l = jnp.where(use_lo, m_new, m_l)
            l_l = jnp.where(use_lo, l_new, l_l)
            a_l = jnp.where(use_lo, a_new, a_l)
            m_h = jnp.where(use_lo, m_h, m_new)
            l_h = jnp.where(use_lo, l_h, l_new)
            a_h = jnp.where(use_lo, a_h, a_new)
            return (m_l, l_l, a_l, m_h, l_h, a_h), None

        z_m = jnp.full((B, Hkv, rep, qb), neg, jnp.float32)
        z_l = jnp.zeros((B, Hkv, rep, qb), jnp.float32)
        z_a = jnp.zeros((B, Hkv, rep, qb, hd), jnp.float32)
        (m_l, l_l, a_l, m_h, l_h, a_h), _ = jax.lax.scan(
            step, (z_m, z_l, z_a, z_m, z_l, z_a), jnp.arange(nq + 1))
        o_lo = a_l / jnp.maximum(l_l, 1e-30)[..., None]
        o_hi = a_h / jnp.maximum(l_h, 1e-30)[..., None]
        return o_lo, o_hi

    with jax.named_scope("flashattn_wrapped"):
        o_lo, o_hi = jax.lax.map(jax.checkpoint(pair), jnp.arange(nq // 2))
    # reassemble (nq, B, g, r, qb, hd): lo tiles ascending, hi descending
    return jnp.concatenate([o_lo, o_hi[::-1]], axis=0)


def decode_attention(cfg: ModelConfig, q: jax.Array, kfull: jax.Array,
                     vfull: jax.Array, k: jax.Array, v: jax.Array, pos,
                     layer) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention that writes the token's K/V into a stacked
    ``(L, B, Hkv, hd, Smax)`` cache (:func:`kv_spec`) at layer ``layer``.

    q: (B, 1, Hq, hd); k, v: (B, 1, Hkv, hd) — the token's new K/V, written
    at each slot's own position (``pos``: scalar or per-slot (B,); a
    position past the cache writes nothing) and attended in the same call.
    The cache is constrained to shard Smax over the `model` axis — the
    softmax max/sum reductions become psums over the model axis, i.e.
    flash-decode's partial-softmax combine, inserted by SPMD partitioning.

    The write and the score/softmax math live in
    :mod:`repro.kernels.ragged_decode`: the Pallas kernel (TPU, or interpret
    mode under ``ragged_decode.force_pallas``) reads K/V blocks only up to
    each slot's position and writes one block per slot, in place;
    elsewhere the jnp reference does the same write and the masked-dense
    read.  Returns (out (B, 1, Hq*hd), kfull, vfull).
    """
    B, _, Hq, hd = q.shape
    kfull = constrain(kfull, *kv_logical_axes(1))
    vfull = constrain(vfull, *kv_logical_axes(1))
    out, kfull, vfull = ragged_decode_attention(
        q.reshape(B, Hq, hd), kfull, vfull, k[:, 0].astype(kfull.dtype),
        v[:, 0].astype(vfull.dtype), position_vector(pos, B), layer)
    return out.reshape(B, 1, Hq * hd).astype(q.dtype), kfull, vfull


def prefill_chunk_attention(cfg: ModelConfig, q: jax.Array,
                            k_cache: jax.Array, v_cache: jax.Array,
                            start: jax.Array, qlen: jax.Array) -> jax.Array:
    """Chunk-of-queries attention against a ragged batch cache (the chunked
    prefill analogue of :func:`decode_attention`).

    q: (B, T, Hq, hd) — chunk token ``i`` of slot ``b`` sits at absolute
    position ``start[b] + i``; caches: one layer in the cache layout
    (B, Hkv, hd, Smax), already holding the chunk's own K/V rows; ``qlen``:
    live rows per slot (padded rows return zeros).  The score/softmax math
    lives in :mod:`repro.kernels.ragged_prefill`, which reads
    (B, Hkv, Smax, hd) blocks: the layer is handed to it swapped (one
    layer's copy per chunk).  Behind the same A/B guard as decode
    attention, the Pallas kernel (TPU, or interpret mode under
    ``ragged_prefill.force_pallas``) streams K/V blocks only up to each
    slot's ``start + qlen - 1`` horizon; elsewhere the jnp reference keeps
    the single-device path byte-stable.
    """
    B, T, Hq, _ = q.shape
    k_cache = constrain(k_cache, *kv_logical_axes(0)).swapaxes(-1, -2)
    v_cache = constrain(v_cache, *kv_logical_axes(0)).swapaxes(-1, -2)
    out = ragged_prefill_attention(q, k_cache, v_cache, start, qlen)
    return out.reshape(B, T, Hq * q.shape[-1]).astype(q.dtype)


@dataclasses.dataclass
class AttnOut:
    x: jax.Array
    k: jax.Array | None = None     # new K/V for cache insertion, in the
    v: jax.Array | None = None     # cache layout (B, Hkv, hd, S) (kv_spec)


def attention_decode_inplace(cfg: ModelConfig, p: Params, x: jax.Array,
                             kfull: jax.Array, vfull: jax.Array,
                             layer_idx, pos, rope: bool = True):
    """One-token attention updating the STACKED (L, B, Hkv, hd, Smax)
    caches in place: the decode kernel writes only the token's column of
    layer ``layer_idx`` and reads the layer through its index map, so a
    scan carrying the full cache neither slices a layer out nor scatters
    into it.

    ``pos`` may be a scalar or a per-slot ``(B,)`` vector (ragged continuous
    batching: every slot decodes at its own position)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    B = x.shape[0]
    pos_vec = position_vector(pos, B)
    positions = pos_vec[:, None]
    q, k, v = _qkv(cfg, p, x, x, positions, positions, rope)
    out, kfull, vfull = decode_attention(cfg, q, kfull, vfull, k, v, pos_vec,
                                         layer_idx)
    out = out @ p["wo"].astype(cdt)
    return constrain(out, "batch", None, None), kfull, vfull


def attention_prefill_chunk_inplace(cfg: ModelConfig, p: Params,
                                    x: jax.Array, kfull: jax.Array,
                                    vfull: jax.Array, layer_idx,
                                    start: jax.Array, qlen: jax.Array,
                                    positions: jax.Array,
                                    rope: bool = True):
    """Chunk-of-tokens attention updating the STACKED (L, B, Hkv, hd, Smax)
    caches in place — the chunked-prefill analogue of
    :func:`attention_decode_inplace`.  ``x``: (B, T, D) chunk activations;
    ``positions``: (B, T) absolute positions (``start[:, None] +
    arange(T)``); padded rows (``i >= qlen[b]``) scatter out of bounds and
    are dropped, so they never land in the cache."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    B, T, _ = x.shape
    q, k, v = _qkv(cfg, p, x, x, positions, positions, rope)
    Smax = kfull.shape[-1]
    batch_ix = jnp.arange(B)[:, None]
    live = jnp.arange(T)[None, :] < qlen[:, None]
    safe_pos = jnp.where(live, positions, Smax)       # OOB rows are dropped
    kfull = kfull.at[layer_idx, batch_ix, :, :, safe_pos].set(
        k.astype(kfull.dtype), mode="drop")
    vfull = vfull.at[layer_idx, batch_ix, :, :, safe_pos].set(
        v.astype(vfull.dtype), mode="drop")
    kc = jax.lax.dynamic_index_in_dim(kfull, layer_idx, 0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(vfull, layer_idx, 0, keepdims=False)
    out = prefill_chunk_attention(cfg, q, kc.astype(cdt), vc.astype(cdt),
                                  start, qlen)
    out = out @ p["wo"].astype(cdt)
    return constrain(out, "batch", None, None), kfull, vfull


def attention_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
                    positions: jax.Array,
                    mode: str = "full",                 # full | decode
                    kv_src: jax.Array | None = None,    # cross-attn source
                    k_cache: jax.Array | None = None,
                    v_cache: jax.Array | None = None,
                    pos=None,
                    rope: bool = True,
                    causal: bool | None = None) -> AttnOut:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    cross = kv_src is not None
    causal = cfg.causal if causal is None else causal
    if mode == "decode" and not cross:
        # project one token and write it into the per-layer cache, handed
        # to the decode kernel as a one-layer stack and returned as k, v.
        # pos may be scalar or per-slot (B,): each slot writes and masks at
        # its own position (ragged continuous batching)
        q, k, v = _qkv(cfg, p, x, x, positions, positions, rope)
        out, kc, vc = decode_attention(cfg, q, k_cache[None], v_cache[None],
                                       k, v, pos, 0)
        out = out @ p["wo"].astype(cdt)
        return AttnOut(x=constrain(out, "batch", None, None), k=kc[0],
                       v=vc[0])
    if mode == "decode" and cross:
        # cross-attn at decode: static KV from the prefill cache, every
        # row live and nothing written
        q, _, _ = _qkv(cfg, p, x, x[:, :1], positions, positions, False)
        B, _, Hq, hd = q.shape
        last = jnp.full((B,), k_cache.shape[-1] - 1, jnp.int32)
        out = decode_attend_ref(q.reshape(B, Hq, hd), k_cache.astype(cdt),
                                v_cache.astype(cdt), last)
        out = out.reshape(B, 1, Hq * hd).astype(cdt)
        return AttnOut(x=(out @ p["wo"].astype(cdt)))
    src = x if not cross else kv_src.astype(cdt)
    kv_pos = positions if not cross else jnp.arange(src.shape[1])
    q, k, v = _qkv(cfg, p, x, src, positions, kv_pos, rope and not cross)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    out = blocked_attention(cfg, q, k, v, causal=causal and not cross)
    out = out @ p["wo"].astype(cdt)
    return AttnOut(x=constrain(out, "batch", None, None),
                   k=to_kv_layout(k), v=to_kv_layout(v))


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key) -> tuple[Params, Specs]:
    D, F = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    if cfg.act == "silu":
        p["w_gate"], s["w_gate"] = dense_init(ks[0], D, F, "fsdp", "ff", dt)
        p["w_up"], s["w_up"] = dense_init(ks[1], D, F, "fsdp", "ff", dt)
        p["w_down"], s["w_down"] = dense_init(ks[2], F, D, "ff", "fsdp", dt)
    else:
        p["w_in"], s["w_in"] = dense_init(ks[0], D, F, "fsdp", "ff", dt)
        p["b_in"], s["b_in"] = jnp.zeros((F,), dt), ("ff",)
        p["w_out"], s["w_out"] = dense_init(ks[1], F, D, "ff", "fsdp", dt)
        p["b_out"], s["b_out"] = jnp.zeros((D,), dt), (None,)
    return p, s


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["w_gate"].astype(cdt)) * (x @ p["w_up"].astype(cdt))
        h = constrain(h, "batch", None, "ff")
        return h @ p["w_down"].astype(cdt)
    h = jax.nn.gelu(x @ p["w_in"].astype(cdt) + p["b_in"].astype(cdt))
    h = constrain(h, "batch", None, "ff")
    return h @ p["w_out"].astype(cdt) + p["b_out"].astype(cdt)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embedding_init(cfg: ModelConfig, key) -> tuple[Params, Specs]:
    dt = jnp.dtype(cfg.param_dtype)
    p, s = {}, {}
    p["embed"] = jax.random.normal(key, (cfg.vocab, cfg.d_model), dt) * 0.02
    s["embed"] = ("vocab", "fsdp")
    if not cfg.tie_embeddings:
        p["lm_head"], s["lm_head"] = dense_init(
            jax.random.fold_in(key, 1), cfg.d_model, cfg.vocab,
            "fsdp", "vocab", dt)
    return p, s


def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = p["embed"].astype(cdt)[tokens]
    return constrain(x, "batch", "seq_sp", None)


def lm_head(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    w = (p["embed"].T if cfg.tie_embeddings else p["lm_head"]).astype(cdt)
    logits = x @ w
    return constrain(logits, "batch", "seq_sp", "vocab")
