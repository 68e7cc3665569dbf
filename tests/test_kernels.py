"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.bitonic_sort.kernel import sort_rows_pallas
from repro.kernels.bitonic_sort.ref import sort_rows_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.ragged_decode import ops as ragged_decode_ops
from repro.kernels.ragged_decode.kernel import read_block_rows
from repro.kernels.ragged_decode.ref import ragged_decode_ref
from repro.kernels.ragged_prefill import ops as ragged_prefill_ops
from repro.kernels.ragged_prefill.ref import ragged_prefill_ref
from repro.kernels.stream_copy.kernel import (stream_copy_pallas,
                                              stream_scale_add_pallas)
from repro.kernels.stream_copy.ref import stream_scale_add_ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 512, 128, 128, 128, 256),
    (512, 256, 256, 256, 128, 128),
    (128, 1024, 256, 64, 128, 512),
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, bm, bn, bk, dtype):
    x = jnp.asarray(RNG.standard_normal((m, k)), dtype)
    y = jnp.asarray(RNG.standard_normal((k, n)), dtype)
    out = matmul_pallas(x, y, block_m=bm, block_n=bn, block_k=bk,
                        interpret=True)
    ref = matmul_ref(x, y)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * np.sqrt(k))


@pytest.mark.parametrize("B,Hq,Hkv,S,hd,bq,bk", [
    (1, 2, 2, 64, 32, 32, 32),       # MHA
    (2, 4, 2, 64, 32, 16, 32),       # GQA rep 2
    (1, 8, 2, 128, 64, 64, 32),      # GQA rep 4
    (2, 2, 1, 96, 16, 32, 48),       # uneven blocks
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, S, hd, bq, bk, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Hq, S, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, S, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, S, hd)), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("rows,n,br", [(8, 128, 8), (16, 256, 4),
                                       (4, 1024, 2)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_bitonic_sort_sweep(rows, n, br, dtype):
    if dtype == np.int32:
        x = jnp.asarray(RNG.integers(-1000, 1000, (rows, n)), jnp.int32)
    else:
        x = jnp.asarray(RNG.standard_normal((rows, n)), jnp.float32)
    out = sort_rows_pallas(x, block_rows=br, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(sort_rows_ref(x)))


@pytest.mark.parametrize("n,block", [(1 << 14, 4096), (1 << 16, 1 << 16)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_stream_sweep(n, block, dtype):
    x = jnp.asarray(RNG.standard_normal(n), dtype)
    y = jnp.asarray(RNG.standard_normal(n), dtype)
    out = stream_copy_pallas(x, block=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(x, np.float32))
    got = stream_scale_add_pallas(x, y, 0.9, 0.1, block=block, interpret=True)
    ref = stream_scale_add_ref(x, y, 0.9, 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def _decode_case(L, layer, Hq, Hkv, Smax, hd, bk, pos, tag=""):
    # ids read B-Hq-Hkv-Smax-hd-bk, as the cases were named before the
    # cache was stacked
    return pytest.param(L, layer, Hq, Hkv, Smax, hd, bk, pos,
                        id=f"{len(pos)}-{Hq}-{Hkv}-{Smax}-{hd}-{bk}{tag}")


@pytest.mark.parametrize("L,layer,Hq,Hkv,Smax,hd,bk,pos", [
    # GQA rep 2: a block start and a block end
    _decode_case(2, 1, 4, 2, 64, 32, 32, (32, 31)),
    # MHA, 2 blocks: a block start, a block end, the last row
    _decode_case(2, 1, 4, 4, 96, 16, 48, (48, 47, 95)),
    # layer 0 and a layer above 0: an idle slot (pinned at 0) and a
    # position past the cache (dropped) besides
    _decode_case(2, 0, 4, 2, 64, 32, 16, (16, 15, 63, 0, 70), "-layer0"),
    _decode_case(3, 2, 4, 2, 64, 32, 16, (32, 47, 63, 0, 64), "-layer2"),
    _decode_case(1, 0, 6, 2, 32, 16, 128, (0, 31, 17), "-one-block"),
    _decode_case(1, 0, 4, 2, 96, 16, 64, (5,), "-not-dividing"),
    # mostly idle slots (pos 0-3) around two live ones over several blocks
    _decode_case(2, 1, 4, 2, 128, 16, 16, (0, 1, 77, 2, 3, 0, 120, 1),
                 "-mostly-idle"),
    # the last row of a read block and the first of the next
    _decode_case(2, 1, 4, 2, 128, 16, 32, (31, 32, 0, 95, 96),
                 "-block-edges"),
    # the cache's last row and a position past it, in one batch
    _decode_case(2, 1, 4, 2, 64, 16, 32, (63, 64, 0, 100, 63),
                 "-cache-end"),
    # qwen2-0.5b's heads (rep 7, hd 64) in 256-row blocks
    _decode_case(2, 1, 14, 2, 512, 64, 256,
                 (0, 130, 300, 511, 1, 255, 256, 600, 127, 128),
                 "-qwen2-heads"),
    # the block the shapes give (f32: 128 rows at Smax 1024)
    _decode_case(1, 0, 14, 2, 1024, 64, None, (700, 0, 1023, 5, 256),
                 "-shape-block"),
    # smollm-135m's heads: Hkv 3, rep 3
    _decode_case(2, 0, 9, 3, 256, 64, 128, (0, 200, 3, 127, 128),
                 "-smollm-heads"),
])
def test_ragged_decode_parity(L, layer, Hq, Hkv, Smax, hd, bk, pos):
    """Pallas ragged decode attention (interpret mode, via force_pallas)
    matches the jnp oracle at mixed per-slot positions, and writes each
    slot's new K/V column at its position of ``layer`` and nothing else:
    every other layer, slot and row comes back byte-identical."""
    B = len(pos)
    q = jnp.asarray(RNG.standard_normal((B, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((L, B, Hkv, hd, Smax)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((L, B, Hkv, hd, Smax)), jnp.float32)
    kn = jnp.asarray(RNG.standard_normal((B, Hkv, hd)), jnp.float32)
    vn = jnp.asarray(RNG.standard_normal((B, Hkv, hd)), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    args = (q, k, v, kn, vn, pos, layer)
    if bk is not None and Smax % min(bk, Smax):
        # an aliased cache cannot be padded: the block must divide Smax
        with ragged_decode_ops.force_pallas(), pytest.raises(ValueError):
            ragged_decode_ops.ragged_decode_attention(*args, block_k=bk)
        return
    with ragged_decode_ops.force_pallas():
        got, gk, gv = ragged_decode_ops.ragged_decode_attention(
            *args, block_k=bk)
    ref, rk, rv = ragged_decode_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)
    for cache, new, outs in ((k, kn, (gk, rk)), (v, vn, (gv, rv))):
        want = np.array(cache)
        for b, p in enumerate(np.asarray(pos)):
            if p < Smax:
                want[layer, b, :, :, p] = np.asarray(new[b])
        for out in outs:
            np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("n_kv,hd,smax,itemsize,rows", [
    (2, 64, 4096, 2, 256),    # qwen2-0.5b, bf16: 128 KB blocks
    (2, 64, 4096, 4, 128),    # the same heads in float32
    (3, 64, 2048, 2, 128),    # smollm-135m: 170 rows fit, 128 divide
    (8, 128, 4096, 2, 128),   # no 128-row block fits: 128 all the same
    (2, 64, 384, 2, 128),     # 256 fits but does not divide 384
    (2, 16, 64, 4, 64),       # a cache of at most 128 rows is one block
])
def test_ragged_decode_block_follows_shapes(n_kv, hd, smax, itemsize, rows):
    """The decode kernel's block is the largest multiple of 128 dividing
    the cache length within the K+V byte budget, 128 when none fits, and
    the whole cache when it is at most 128 rows."""
    assert read_block_rows(n_kv, hd, smax, itemsize) == rows


def test_ragged_decode_block_refuses_unaligned_cache():
    with pytest.raises(ValueError):
        read_block_rows(2, 64, 4000, 2)


@pytest.mark.parametrize("B,T,Hq,Hkv,Smax,hd,bk", [
    (2, 8, 4, 2, 64, 32, 32),    # GQA rep 2
    (2, 4, 2, 2, 48, 16, 48),    # MHA, partial chunks
])
def test_ragged_prefill_parity(B, T, Hq, Hkv, Smax, hd, bk):
    """Pallas chunked ragged prefill attention (interpret mode) matches the
    jnp oracle with per-slot chunk origins and ragged live lengths."""
    q = jnp.asarray(RNG.standard_normal((B, T, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Smax, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Smax, hd)), jnp.float32)
    start = jnp.asarray(RNG.integers(0, Smax - T, (B,)), jnp.int32)
    qlen = jnp.asarray(RNG.integers(1, T + 1, (B,)), jnp.int32)
    with ragged_prefill_ops.force_pallas():
        got = ragged_prefill_ops.ragged_prefill_attention(q, k, v, start,
                                                          qlen, block_k=bk)
    ref = ragged_prefill_ref(q, k, v, start, qlen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("S,qb", [(64, 16), (128, 32)])
def test_wrapped_causal_matches_blocked(S, qb):
    """Load-balanced triangular causal blocking (causal_scheme='wrapped')
    is numerically identical to the masked blocked schedule, incl. grads."""
    import dataclasses
    import jax
    from repro.configs.base import ModelConfig
    from repro.models.layers import blocked_attention
    cfg_b = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                        n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                        q_block=qb, kv_block=2 * qb, compute_dtype="float32")
    cfg_w = dataclasses.replace(cfg_b, causal_scheme="wrapped")
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, S, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, S, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, S, 2, 8)), jnp.float32)
    a = blocked_attention(cfg_b, q, k, v, causal=True)
    b = blocked_attention(cfg_w, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    ga = jax.grad(lambda q: blocked_attention(cfg_b, q, k, v, True).sum())(q)
    gb = jax.grad(lambda q: blocked_attention(cfg_w, q, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=1e-4, atol=1e-5)
