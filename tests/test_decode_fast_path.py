"""Fused decode fast path: donated-cache k-token scan decode (greedy argmax
on device) and the ragged Pallas decode-attention kernel must produce
byte-identical greedy token streams vs the legacy per-step path, on every
model family — including a mid-chunk finish (max_new not divisible by the
chunk) and a session export/import after the cache has been donated."""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.kernels.ragged_decode import force_pallas, ragged_decode_attention
from repro.kernels.ragged_decode.ref import ragged_decode_ref
from repro.models import get_model
from repro.serve import Request, ServeEngine

FAMILY_ARCHS = ("qwen2-0.5b", "granite-moe-1b-a400m", "mamba2-130m",
                "jamba-v0.1-52b", "llama-3.2-vision-90b")

MAX_SEQ = 32


def _setup(arch, seed=0):
    cfg = get_config(arch, reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(seed))
    return cfg, m, params


def _requests(cfg, rng, n, max_new):
    reqs = []
    for i in range(n):
        extras = {}
        if cfg.family == "vlm":
            extras["image_embeds"] = np.asarray(
                jax.random.normal(jax.random.PRNGKey(7),
                                  (cfg.n_image_tokens, cfg.d_model)))
        reqs.append(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6),
                            max_new=max_new, extras=extras))
    return reqs


def _decode_all(m, params, reqs, *, fused, chunk=1, max_batch=2):
    engine = ServeEngine(m, params, max_batch=max_batch, max_seq=MAX_SEQ,
                         decode_chunk=chunk, fused=fused)
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained(max_steps=200)
    assert all(r.done for r in reqs)
    return [list(r.out_tokens) for r in reqs]


def _clone(reqs):
    return [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=r.max_new,
                    extras=dict(r.extras)) for r in reqs]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
@pytest.mark.parametrize("chunk", (1, 4))
def test_fused_scan_decode_token_identity(arch, chunk):
    """Fused k-token decode (donated cache, device argmax) vs the legacy
    per-step path.  max_new=6 is not divisible by 4, so chunk=4 exercises
    the mid-chunk finish: the engine must truncate the surplus tokens the
    chunk decoded past max_new."""
    cfg, m, params = _setup(arch)
    rng = np.random.default_rng(0)
    ref_reqs = _requests(cfg, rng, 2, max_new=6)
    ref = _decode_all(m, params, ref_reqs, fused=False)
    got = _decode_all(m, params, _clone(ref_reqs), fused=True, chunk=chunk)
    assert got == ref, (arch, chunk, got, ref)
    assert all(len(t) == 6 for t in got)         # surplus truncated exactly


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_export_import_after_donation_token_identity(arch):
    """A session exported AFTER the donated fast path has been running (the
    original cache buffers are long dead) must carry valid host-side state:
    resuming it on another fused engine reproduces the unmigrated greedy
    stream."""
    cfg, m, params = _setup(arch, seed=1)
    rng = np.random.default_rng(1)
    ref_reqs = _requests(cfg, rng, 1, max_new=8)
    ref = _decode_all(m, params, ref_reqs, fused=False)

    mig = _clone(ref_reqs)[0]
    a = ServeEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                    decode_chunk=2, fused=True)
    b = ServeEngine(m, params, max_batch=2, max_seq=MAX_SEQ,
                    decode_chunk=2, fused=True)
    a.submit(mig)
    for _ in range(2):                 # 1 prefill token + 2 fused chunks
        a.step()
    assert not mig.done
    sess = a.export_session(mig.rid)
    assert all(isinstance(v, np.ndarray) for v in sess.cache.values())
    b.import_session(sess)
    b.run_until_drained(max_steps=200)
    assert mig.done
    assert list(mig.out_tokens) == ref[0], (arch, mig.out_tokens, ref[0])


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_ragged_pallas_kernel_token_identity(arch):
    """The Pallas ragged decode-attention kernel (interpret mode on CPU),
    driven through the full fused decode, matches the per-step reference
    path token for token.  The kernel choice is baked in at trace time, so
    a fresh Model (fresh jit cache) is built inside the force context."""
    cfg, m, params = _setup(arch, seed=2)
    rng = np.random.default_rng(2)
    ref_reqs = _requests(cfg, rng, 2, max_new=4)
    ref = _decode_all(m, params, ref_reqs, fused=False)
    with force_pallas():
        m2 = get_model(cfg)            # fresh traces pick up the kernel
        got = _decode_all(m2, params, _clone(ref_reqs), fused=True, chunk=2)
    assert got == ref, (arch, got, ref)


def test_ragged_kernel_matches_reference_numerically():
    """Direct op-level check on a stacked cache: GQA, ragged per-slot
    positions at layer 1 of 2; a cache length that does not divide the
    k-block is refused (an aliased cache cannot be padded)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    for (B, Smax, Hq, Hkv, hd, bk) in ((4, 32, 8, 2, 16, 8),
                                       (3, 19, 6, 6, 8, 8)):
        q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, B, Hkv, hd, Smax)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, B, Hkv, hd, Smax)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(B, Hkv, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, Hkv, hd)), jnp.float32)
        pos = jnp.asarray(rng.integers(0, Smax, B), jnp.int32)
        args = (q, k, v, kn, vn, pos, 1)
        if Smax % bk:
            with force_pallas(), pytest.raises(ValueError, match="multiple"):
                ragged_decode_attention(*args, block_k=bk)
            continue
        ref = ragged_decode_ref(*args)
        with force_pallas():
            out = ragged_decode_attention(*args, block_k=bk)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-5, atol=1e-5)
        for got, want in zip(out[1:], ref[1:]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and the default (CPU) route IS the reference
        got = ragged_decode_attention(*args)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_donated_cache_is_consumed():
    """Contract check: after a fused decode dispatch the old cache buffers
    are dead (donated) — holding on to them is a bug the engine must never
    have.  Guards against silently losing `donate_argnums` in a refactor
    (the copy-per-token would come back with no functional symptom)."""
    import jax.numpy as jnp
    cfg, m, params = _setup("smollm-135m")
    spec = m.cache_spec(2, MAX_SEQ)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    _, _, _, cache2 = m.decode_fused(params, tok, pos, cache, 2)
    jax.tree.leaves(cache2)[0].block_until_ready()
    leaf = jax.tree.leaves(cache)[0]
    with pytest.raises(RuntimeError):
        np.asarray(leaf)               # donated: buffer deleted
