"""Ragged decode attention (one query token per sequence, GQA) as a Pallas
TPU kernel.

The serving decode step is ragged: every batch slot sits at its own position,
so a dense implementation scores all of ``Smax`` and masks — a slot 10 tokens
into generation pays for a 4k-row cache read.  This kernel iterates K/V
blocks only up to each slot's position:

* grid ``(B, Hkv, nk)``, k-blocks innermost; the online-softmax state
  (m, l, acc) lives in VMEM scratch across the k sweep and the output tile is
  written once at the last k step (same discipline as the flash kernel);
* the per-slot positions arrive as a **scalar-prefetch** operand
  (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`), so they are
  readable both in the kernel body (for the tail-block mask) and in the K/V
  ``index_map`` — blocks past ``pos[b]`` clamp their index to the last live
  block, which makes the pipeline re-issue an already-resident tile instead
  of DMA'ing dead cache rows, and ``pl.when`` skips their compute entirely;
* GQA is folded into the q/out block shape ``(rep, hd)`` with K/V indexed by
  the Hkv grid axis — no KV head replication ever hits HBM;
* the cache is head-major ``(B, Hkv, Smax, hd)``, so a K/V block
  ``(1, 1, bk, hd)`` has ``(bk, hd)`` as its last two dimensions — the
  layout Mosaic's tiling rule accepts (a sequence-major cache would need a
  ``(bk, 1, hd)`` block whose second-minor 1 is neither a multiple of 8
  nor the full Hkv).

VMEM per step: q (rep,hd) + k,v (bk,hd) + scores (rep,bk) f32 + acc (rep,hd)
f32 — tiny; the kernel is bandwidth-bound on the cache read, which is exactly
the traffic the ragged clamp eliminates.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, scale: float, bk: int,
                          n_k: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos_b = pos_ref[b]                                    # newest-token index
    k_start = ki * bk

    def _step():
        q = q_ref[0, 0]                                   # (rep, hd)
        k = k_ref[0, 0]                                   # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rep, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos <= pos_b, s, NEG_INF)          # ragged tail mask
        m_prev = m_ref[...]                               # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # blocks strictly past this slot's position hold no live entries:
    # skip their compute (their DMA was already clamped by the index_map)
    pl.when(k_start <= pos_b)(_step)

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def ragged_decode_pallas(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, pos: jax.Array, *,
                         block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, rep, hd); k,v: (B, Hkv, Smax, hd); pos: (B,) int32
    (index of each slot's newest live token).  Returns (B, Hkv, rep, hd)
    float32."""
    B, Hkv, rep, hd = q.shape
    Smax = k_cache.shape[2]
    bk = min(block_k, Smax)
    pad = (-Smax) % bk
    if pad:                       # padded rows sit past any pos: masked off
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    n_k = (Smax + pad) // bk

    def kv_map(b, g, ki, pos_ref):
        # clamp dead blocks onto the slot's last live block: the pipeline
        # re-issues a resident tile instead of streaming unused cache rows
        return (b, g, jnp.minimum(ki, pos_ref[b] // bk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, g, ki, pos_ref: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, g, ki, pos_ref: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),    # m
            pltpu.VMEM((rep, 1), jnp.float32),    # l
            pltpu.VMEM((rep, hd), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_decode_kernel,
                          scale=1.0 / math.sqrt(hd), bk=bk, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32),
        interpret=interpret,
        name="ragged_decode",
    )(pos.astype(jnp.int32), q, k_cache, v_cache)
