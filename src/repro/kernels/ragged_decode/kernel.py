"""Ragged decode attention (one query token per sequence, GQA) as a Pallas
TPU kernel that also writes the token's K/V into the stacked cache.

The serving decode step is ragged: every batch slot sits at its own position,
so a dense implementation scores all of ``Smax`` and masks — a slot 10 tokens
into generation pays for a 4k-row cache read.  This kernel iterates K/V
blocks only up to each slot's position:

* grid ``(B, Hkv, nk)``, k-blocks innermost; the online-softmax state
  (m, l, acc) lives in VMEM scratch across the k sweep and the output tile is
  written once at the last k step (same discipline as the flash kernel);
* the per-slot positions and the layer index arrive as **scalar-prefetch**
  operands (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`),
  so they are readable both in the kernel body (for the tail-block mask and
  the write) and in the K/V ``index_map`` — blocks past ``pos[b]`` clamp
  their index to the last live block, which makes the pipeline re-issue an
  already-resident tile instead of DMA'ing dead cache rows, and ``pl.when``
  skips their compute entirely;
* GQA is folded into the q/out block shape ``(rep, hd)`` with K/V indexed by
  the Hkv grid axis — no KV head replication ever hits HBM;
* the kernel takes the whole stacked cache ``(L, B, Hkv, hd, Smax)``, the
  layout the TPU stores it in (sequence on the lanes, so ``hd`` 64 is not
  padded to 128), and addresses the layer through the index map: no layer
  slice and no relayout of the cache happen outside it;
* it writes the token's K/V column into the block holding ``pos[b]`` and
  hands the caches back through ``input_output_aliases``: the only cache
  bytes written are that one block per slot and head, and the token
  attends to its own K/V in the same call.  The written block goes out by
  one manual DMA per (slot, head), started where it is built and awaited
  at the sweep's last step, and the token's K and V come in as one
  ``(hd, 2)`` block: every pipelined operand costs each of the grid's
  ``B * Hkv * nk`` steps some scalar work, and on a v5e at long-chat's
  shapes (24 layers, B 64, Smax 4096, 6 live slots) the kernel with
  pipelined K and V outputs and separate K/V-new inputs took 1.30 ms a
  call against 0.80 ms for this one.

VMEM per step: q (rep,hd) + k,v (hd,bk) + scores (rep,bk) f32 + acc (rep,hd)
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


def _ragged_decode_kernel(pos_ref, layer_ref, q_ref, k_ref, v_ref, new_ref,
                          o_ref, k_hbm, v_hbm, m_ref, l_ref, acc_ref, k_blk,
                          v_blk, sem, *, scale: float, bk: int, n_k: int):
    b = pl.program_id(0)
    g = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos_b = pos_ref[b]                                    # newest-token index
    k_start = ki * bk
    # the block the token's column lands in; a position past the cache
    # writes nothing, and its last block is then the one passed through
    w_blk = jnp.minimum(pos_b // bk, n_k - 1)

    def kpos():
        return k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

    def writes():
        start = pl.multiple_of(w_blk * bk, bk)
        at = (layer_ref[0], b, g, slice(None), pl.ds(start, bk))
        return (pltpu.make_async_copy(k_blk, k_hbm.at[at], sem.at[0]),
                pltpu.make_async_copy(v_blk, v_hbm.at[at], sem.at[1]))

    def _step(k, v):                                      # k, v: (hd, bk)
        q = q_ref[0, 0]                                   # (rep, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (rep, bk)
        s = jnp.where(kpos() <= pos_b, s, NEG_INF)        # ragged tail mask
        m_prev = m_ref[...]                               # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # blocks before the written one are live and resident as they are;
    # blocks past it hold no live entries: their compute is skipped (and
    # their DMA was already clamped by the index_map)
    @pl.when(ki < w_blk)
    def _read():
        _step(k_ref[0, 0, 0], v_ref[0, 0, 0])

    @pl.when(ki == w_blk)
    def _write_and_read():
        # ki == w_blk <= pos // bk, so the resident block starts at k_start
        col = kpos() == pos_b
        new = new_ref[0, 0]                               # (hd, 2): K, V
        k = jnp.where(col, new[:, 0:1], k_ref[0, 0, 0])
        v = jnp.where(col, new[:, 1:2], v_ref[0, 0, 0])
        k_blk[...] = k
        v_blk[...] = v
        for copy in writes():
            copy.start()
        _step(k, v)

    @pl.when(ki == n_k - 1)
    def _finish():
        for copy in writes():
            copy.wait()
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def ragged_decode_pallas(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, k_new: jax.Array,
                         v_new: jax.Array, pos: jax.Array, layer, *,
                         block_k: int = 128, interpret: bool = False):
    """q: (B, Hkv, rep, hd); k,v caches: stacked (L, B, Hkv, hd, Smax);
    k_new, v_new: (B, Hkv, hd), the token's K/V; pos: (B,) int32 (index of
    each slot's newest token, where it is written; a position past the
    cache writes nothing); layer: int32 scalar.  Returns (out (B, Hkv, rep,
    hd) float32, k_cache, v_cache) with the caches updated in place
    (aliased).  ``Smax`` must be a multiple of the block size
    ``min(block_k, Smax)``: an aliased cache cannot be padded."""
    B, Hkv, rep, hd = q.shape
    Smax = k_cache.shape[-1]
    bk = min(block_k, Smax)
    if Smax % bk:
        raise ValueError(
            f"ragged_decode: cache length {Smax} is not a multiple of the "
            f"k-block {bk}; size max_seq to a multiple of {block_k}")
    n_k = Smax // bk

    def kv_map(b, g, ki, pos_ref, layer_ref):
        # clamp dead blocks onto the slot's last live block: the pipeline
        # re-issues a resident tile instead of streaming unused cache rows
        return (layer_ref[0], b, g, 0, jnp.minimum(ki, pos_ref[b] // bk))

    head_map = lambda b, g, ki, pos_ref, layer_ref: (b, g, 0, 0)
    kv_block = (1, 1, 1, hd, bk)
    in_place = pl.BlockSpec(memory_space=pl.ANY)          # written by DMA
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), head_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec((1, 1, hd, 2), head_map),
        ],
        out_specs=[pl.BlockSpec((1, 1, rep, hd), head_map), in_place,
                   in_place],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),    # m
            pltpu.VMEM((rep, 1), jnp.float32),    # l
            pltpu.VMEM((rep, hd), jnp.float32),   # acc
            pltpu.VMEM((hd, bk), k_cache.dtype),  # written K block
            pltpu.VMEM((hd, bk), v_cache.dtype),  # written V block
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    new = jnp.stack([k_new, v_new], axis=-1).astype(k_cache.dtype)
    return pl.pallas_call(
        functools.partial(_ragged_decode_kernel,
                          scale=1.0 / math.sqrt(hd), bk=bk, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hkv, rep, hd), jnp.float32),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)),
        # operands: pos, layer, q, k_cache, v_cache, new
        input_output_aliases={3: 1, 4: 2},
        interpret=interpret,
        name="ragged_decode",
    )(pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), q,
      k_cache, v_cache, new)
