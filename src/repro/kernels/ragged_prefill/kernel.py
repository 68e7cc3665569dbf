"""Chunked ragged prefill attention (a fixed-size block of query tokens per
sequence, GQA) as a Pallas TPU kernel.

Disaggregated serving prefills long prompts in fixed-size chunks so a
prefill replica never holds the batch hostage for a 32k prompt: each chunk's
queries attend causally against everything already resident in the slot's
KV cache (the prior chunks plus the chunk itself).  A dense implementation
scores all of ``Smax`` per chunk; this kernel iterates K/V blocks only up to
each slot's live horizon:

* grid ``(B, Hkv, nk)``, k-blocks innermost; online-softmax state (m, l,
  acc) lives in VMEM scratch across the k sweep, the output tile written
  once at the last k step — the same discipline as ``ragged_decode``;
* **two scalar-prefetch operands** (`start`, `qlen` — chunk origin and live
  length per slot) feed both the kernel body (causal + ragged row masks)
  and the K/V ``index_map``: blocks past ``start[b] + qlen[b] - 1`` clamp
  to the last live block, so the pipeline re-issues a resident tile instead
  of DMA'ing rows no query can see, and ``pl.when`` skips their compute;
* GQA folds into the q/out block ``(T*rep, hd)`` — query row ``i`` is chunk
  token ``i // rep`` at absolute position ``start[b] + i // rep``; K/V are
  indexed by the Hkv grid axis, so no KV-head replication ever hits HBM;
* the cache is head-major ``(B, Hkv, Smax, hd)`` so each K/V block
  ``(1, 1, bk, hd)`` tiles its last two dimensions the way Mosaic requires.

Padded chunk rows (``i // rep >= qlen[b]``) are masked out of every score;
their ``l`` stays 0 and the epilogue's ``acc / max(l, eps)`` writes exact
zeros, matching the jnp reference's explicit zeroing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_prefill_kernel(start_ref, qlen_ref, q_ref, k_ref, v_ref, o_ref,
                           m_ref, l_ref, acc_ref, *, scale: float, bk: int,
                           n_k: int, rep: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start_b = start_ref[b]                     # chunk's first absolute pos
    qlen_b = qlen_ref[b]                       # live query rows this chunk
    last = start_b + qlen_b - 1                # newest cache row any query sees
    k_start = ki * bk
    tr = m_ref.shape[0]                        # T * rep folded rows

    def _step():
        q = q_ref[0, 0]                                   # (T*rep, hd)
        k = k_ref[0, 0]                                   # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (T*rep, bk)
        row = jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0) // rep
        qpos = start_b + row                              # (T*rep, 1)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        # causal against the whole resident cache + ragged row mask for
        # padded chunk rows
        mask = (kpos <= qpos) & (row < qlen_b)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                               # (T*rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # a fully-masked row (chunk padding) keeps m at NEG_INF; exp(s - m)
        # would be exp(0) = 1 there, so the mask must zero p explicitly —
        # unlike the decode kernel, where every live block has a live column
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # blocks strictly past the chunk's horizon hold rows no query can see:
    # skip their compute (their DMA was already clamped by the index_map)
    pl.when((k_start <= last) & (qlen_b > 0))(_step)

    @pl.when(ki == n_k - 1)
    def _finish():
        # padded rows never accumulated: l == 0 there, so the guarded
        # divide writes exact zeros (the reference zeroes them explicitly)
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def ragged_prefill_pallas(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, start: jax.Array,
                          qlen: jax.Array, *, rep: int, block_k: int = 128,
                          interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, T*rep, hd) GQA-folded chunk queries (row ``i`` is chunk
    token ``i // rep``); k,v: (B, Hkv, Smax, hd); start, qlen: (B,) int32
    (chunk origin / live rows per slot).  Returns (B, Hkv, T*rep, hd)
    float32 with padded rows zeroed."""
    B, Hkv, tr, hd = q.shape
    Smax = k_cache.shape[2]
    bk = min(block_k, Smax)
    pad = (-Smax) % bk
    if pad:                       # padded rows sit past any horizon: masked
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    n_k = (Smax + pad) // bk

    def kv_map(b, g, ki, start_ref, qlen_ref):
        # clamp dead blocks onto the chunk's last visible block: the
        # pipeline re-issues a resident tile instead of streaming rows
        # past start + qlen - 1 (max(0, .) guards empty padded slots)
        last = jnp.maximum(start_ref[b] + qlen_ref[b] - 1, 0)
        return (b, g, jnp.minimum(ki, last // bk), 0)

    def fold_map(b, g, ki, start_ref, qlen_ref):
        return (b, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, tr, hd), fold_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, tr, hd), fold_map),
        scratch_shapes=[
            pltpu.VMEM((tr, 1), jnp.float32),    # m
            pltpu.VMEM((tr, 1), jnp.float32),    # l
            pltpu.VMEM((tr, hd), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_prefill_kernel,
                          scale=1.0 / math.sqrt(hd), bk=bk, n_k=n_k, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, tr, hd), jnp.float32),
        interpret=interpret,
        name="ragged_prefill",
    )(start.astype(jnp.int32), qlen.astype(jnp.int32), q, k_cache, v_cache)
