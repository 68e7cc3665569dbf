"""Ragged decode attention (one query token per sequence, GQA) as a Pallas
TPU kernel that also writes the token's K/V into the stacked cache.

The serving decode step is ragged: every batch slot sits at its own position,
and most slots of a large batch are idle (pinned at position 0).  The
kernel's cost follows each slot's live K/V rows, not ``B * Smax``:

* the grid is ``(B,)``, one step per slot with all of its KV heads, marked
  ``"arbitrary"`` so the steps run in order and a DMA may span two of them;
  q and the output come and go as pipelined ``(1, Hkv, rep, hd)`` blocks,
  the token's K and V as one ``(1, Hkv, hd, 2)`` block;
* the per-slot positions and the layer index arrive as **scalar-prefetch**
  operands (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`);
  slot ``b`` has ``min(pos[b] // bk, nk - 1) + 1`` live blocks of ``bk``
  rows, and a ``lax.fori_loop`` walks exactly those;
* K and V stay in HBM (``memory_space=pl.ANY``) and come in by **manual
  DMA** from the whole stacked cache ``(L, B, Hkv, hd, Smax)`` (sequence on
  the lanes, so ``hd`` 64 is not padded to 128, and no layer slice or
  relayout happens outside the kernel), one copy each of K and V per
  ``(Hkv, hd, bk)`` block of ``k[layer, b]``;
* the reads are double-buffered across slots: an SMEM cursor walks the
  flattened sequence of (slot, live block) pairs, and each block consumed
  starts the read of the next one, which is the next slot's first block
  when this slot has no more, so an idle slot's read is in flight while
  the slot before it computes;
* the score and value matmuls are batched over the KV heads,
  ``(Hkv, rep, hd) x (Hkv, hd, bk)``, so no KV head is replicated;
* the token's K/V column is merged into the slot's last live block (the
  one holding ``pos[b]``, already resident) and the token attends to it in
  the same call.  Only that block goes back out, by DMA from a write
  buffer into the aliased caches (``input_output_aliases``); the write is
  awaited at the next slot, before the buffer is reused, and at the last
  step.  A position past the cache writes its last block back unchanged,
  so nothing changes.

**The block** ``bk`` follows the shapes (:func:`read_block_rows`): the
largest multiple of 128 rows that divides ``Smax`` and keeps a K+V block
within ``_BLOCK_BYTES`` (at least 128 rows; ``Smax`` when that is
smaller).  An idle slot reads and writes one whole block, so a larger
block saves a live slot's loop steps but costs every idle slot bytes; at
qwen2-0.5b's shapes 256 rows (128 KB) was fastest (PERF.md).  ``block_k``
sets it for the tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_BLOCK_BYTES = 128 * 1024   # one K+V block, all KV heads


def read_block_rows(n_kv: int, hd: int, smax: int, itemsize: int) -> int:
    """The block ``bk`` for a cache of ``n_kv`` heads of width ``hd`` and
    length ``smax``: the largest multiple of 128 dividing ``smax`` whose
    K+V block fits ``_BLOCK_BYTES``, or 128 rows if none does; ``smax``
    itself when it is at most 128."""
    if smax <= LANES:
        return smax
    if smax % LANES:
        raise ValueError(
            f"ragged_decode: cache length {smax} is not a multiple of "
            f"{LANES}; size max_seq to a multiple of {LANES}")
    row = 2 * n_kv * hd * itemsize
    fits = [r for r in range(LANES, smax + 1, LANES)
            if smax % r == 0 and r * row <= _BLOCK_BYTES]
    return max(fits, default=LANES)


def _ragged_decode_kernel(pos_ref, layer_ref, q_ref, k_in, v_in, new_ref,
                          o_ref, k_out, v_out, k_buf, v_buf, k_w, v_w,
                          m_ref, l_ref, acc_ref, read_sem, write_sem, cur_ref,
                          *, scale: float, bk: int, n_k: int, n_slots: int):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def n_live(s):
        return jnp.minimum(pos_ref[s] // bk, n_k - 1) + 1

    def read(s, i, x, op):
        """Start or wait for the read of block ``i`` of slot ``s`` into
        buffer ``x``."""
        at = (layer, s, slice(None), slice(None),
              pl.ds(pl.multiple_of(i * bk, bk), bk))
        for src, buf, sem in ((k_in, k_buf, read_sem.at[0, x]),
                              (v_in, v_buf, read_sem.at[1, x])):
            getattr(pltpu.make_async_copy(src.at[at], buf.at[x], sem), op)()

    # cur_ref: blocks consumed, then the read cursor (slot, block, reads
    # started) over the flattened (slot, live block) sequence
    def start_read():
        s, i, n = cur_ref[1], cur_ref[2], cur_ref[3]

        @pl.when(s < n_slots)
        def _():
            read(s, i, n % 2, "start")
            last = i + 1 == n_live(s)
            cur_ref[1] = jnp.where(last, s + 1, s)
            cur_ref[2] = jnp.where(last, 0, i + 1)
            cur_ref[3] = n + 1

    def next_block(j):
        """Wait for this slot's block ``j``, the next in read order, start
        the read after it into the other buffer, and return the block's
        buffer index."""
        c = cur_ref[0]
        x = c % 2
        read(b, j, x, "wait")
        start_read()
        cur_ref[0] = c + 1
        return x

    def write(j, op):
        dst = (layer, b, slice(None), slice(None),
               pl.ds(pl.multiple_of(j * bk, bk), bk))
        for buf, out, h in ((k_w, k_out, 0), (v_w, v_out, 1)):
            getattr(pltpu.make_async_copy(buf, out.at[dst], write_sem.at[h]),
                    op)()

    @pl.when(b == 0)
    def _prime():
        for i in range(4):
            cur_ref[i] = 0
        start_read()

    pos_b = pos_ref[b]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                          # (Hkv, rep, hd)

    def attend(k, v, live=None):                          # (Hkv, hd, bk)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (Hkv, rep, bk)
        if live is not None:                              # ragged tail
            s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[...]                               # (Hkv, rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (Hkv, rep, hd)
        m_ref[...] = m_new

    last = n_live(b) - 1

    def body(j, carry):
        x = next_block(j)
        attend(k_buf[x], v_buf[x])
        return carry

    jax.lax.fori_loop(0, last, body, 0)

    # the last live block holds pos_b (or, past the cache, passes through):
    # the token's column goes in, lanes past pos_b are masked
    x = next_block(last)
    kpos = last * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    col = kpos == pos_b
    k_new = jnp.where(col, new_ref[0, :, :, 0:1], k_buf[x])
    v_new = jnp.where(col, new_ref[0, :, :, 1:2], v_buf[x])
    live = kpos <= pos_b
    attend(k_new, jnp.where(live, v_new, 0), live)
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)

    # the block goes back out from the write buffer, once the previous
    # slot's write from it is done
    @pl.when(b > 0)
    def _reuse():
        write(0, "wait")

    k_w[...] = k_new
    v_w[...] = v_new
    write(last, "start")

    @pl.when(b == n_slots - 1)
    def _drain():
        write(0, "wait")


def ragged_decode_pallas(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, k_new: jax.Array,
                         v_new: jax.Array, pos: jax.Array, layer, *,
                         block_k: int | None = None,
                         interpret: bool = False):
    """q: (B, Hkv, rep, hd); k,v caches: stacked (L, B, Hkv, hd, Smax);
    k_new, v_new: (B, Hkv, hd), the token's K/V; pos: (B,) int32 (index of
    each slot's newest token, where it is written; a position past the
    cache writes nothing); layer: int32 scalar.  Returns (out (B, Hkv, rep,
    hd) float32, k_cache, v_cache) with the caches updated in place
    (aliased).  The block is :func:`read_block_rows` of the shapes, or
    ``min(block_k, Smax)`` rows when given; ``Smax`` must be a multiple of
    it: an aliased cache cannot be padded."""
    B, Hkv, rep, hd = q.shape
    Smax = k_cache.shape[-1]
    if block_k is None:
        bk = read_block_rows(Hkv, hd, Smax, k_cache.dtype.itemsize)
    else:
        bk = min(block_k, Smax)
    if Smax % bk:
        raise ValueError(
            f"ragged_decode: cache length {Smax} is not a multiple of the "
            f"k-block {bk}; size max_seq to a multiple of {bk}")

    slot = lambda b, *_: (b, 0, 0, 0)
    in_place = pl.BlockSpec(memory_space=pl.ANY)          # moved by DMA
    block = (Hkv, hd, bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, rep, hd), slot),
            in_place,
            in_place,
            pl.BlockSpec((1, Hkv, hd, 2), slot),
        ],
        out_specs=[pl.BlockSpec((1, Hkv, rep, hd), slot), in_place,
                   in_place],
        scratch_shapes=[
            pltpu.VMEM((2, *block), k_cache.dtype),      # K reads
            pltpu.VMEM((2, *block), v_cache.dtype),      # V reads
            pltpu.VMEM(block, k_cache.dtype),            # K write
            pltpu.VMEM(block, v_cache.dtype),            # V write
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),      # m
            pltpu.VMEM((Hkv, rep, 1), jnp.float32),      # l
            pltpu.VMEM((Hkv, rep, hd), jnp.float32),     # acc
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((4,), jnp.int32),                 # read cursor
        ],
    )
    new = jnp.stack([k_new, v_new], axis=-1).astype(k_cache.dtype)
    out, k_cache, v_cache = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, scale=1.0 / math.sqrt(hd),
                          bk=bk, n_k=Smax // bk, n_slots=B),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)),
        # operands: pos, layer, q, k_cache, v_cache, new
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_decode",
    )(pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, k_cache, v_cache, new)
    return out, k_cache, v_cache
