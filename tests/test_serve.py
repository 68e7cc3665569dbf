"""Serving engine: generated tokens must match a direct greedy decode."""

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import get_model
from repro.serve import Request, ServeEngine


def _greedy_reference(m, p, prompt, n_new, vocab):
    toks = list(prompt)
    for _ in range(n_new):
        logits = m.forward(p, {"tokens": jnp.asarray(toks)[None, :]})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_greedy_decode():
    cfg = get_config("qwen2-0.5b", reduced=True)
    m = get_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 8) for _ in range(3)]
    engine = ServeEngine(m, p, max_batch=4, max_seq=32)
    reqs = [Request(rid=i, prompt=pr, max_new=6)
            for i, pr in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained(max_steps=100)
    for r, pr in zip(reqs, prompts):
        assert r.done
        ref = _greedy_reference(m, p, pr, 6, cfg.vocab)
        assert r.out_tokens[:6] == ref, (r.out_tokens, ref)


def test_ragged_admission_mixed_prompt_lengths():
    """Continuous batching: one admission round takes prompts of different
    lengths into one batch (the wave engine admitted only equal-length
    prompts into an empty batch) and still matches greedy decode."""
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (5, 9, 7)]
    engine = ServeEngine(m, p, max_batch=4, max_seq=32)
    reqs = [Request(rid=i, prompt=pr, max_new=5)
            for i, pr in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert engine.active_count() == 3        # all admitted despite raggedness
    engine.run_until_drained(max_steps=100)
    for r, pr in zip(reqs, prompts):
        assert r.done
        ref = _greedy_reference(m, p, pr, 5, cfg.vocab)
        assert r.out_tokens[:5] == ref, (r.rid, r.out_tokens, ref)


def test_admission_into_occupied_batch():
    """A free slot admits a new prompt while other slots are mid-decode —
    no waiting for the batch to drain."""
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    engine = ServeEngine(m, p, max_batch=2, max_seq=32)
    first = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6), max_new=8)
    engine.submit(first)
    engine.step()
    engine.step()
    assert not first.done and engine.active_count() == 1
    late = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 4), max_new=4)
    engine.submit(late)
    engine.step()                            # admits next to the live slot
    assert engine.active_count() == 2
    engine.run_until_drained(max_steps=100)
    for r, n in ((first, 8), (late, 4)):
        assert r.done
        ref = _greedy_reference(m, p, r.prompt, n, cfg.vocab)
        assert r.out_tokens[:n] == ref, (r.rid, r.out_tokens, ref)


def test_step_latency_hook_only_fires_on_decode():
    """A step that only admits (every admission finished at prefill) must
    not feed a zero/stale latency into on_step_latency — the interference
    detector needs a homogeneous decode-only signal."""
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    engine = ServeEngine(m, p, max_batch=2, max_seq=24)
    seen = []
    engine.on_step_latency = seen.append
    engine.step()                            # idle step: no signal
    assert seen == [] and engine.last_step_latency == 0.0
    one = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6), max_new=1)
    engine.submit(one)
    assert engine.step() == 0                # admit-only: done at prefill
    assert one.done and seen == []
    assert engine.last_step_latency == 0.0
    two = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 6), max_new=3)
    engine.submit(two)
    engine.step()                            # real decode: signal fires
    assert len(seen) == 1 and seen[0] > 0.0
    assert engine.last_step_latency == seen[0]


def test_engine_queueing_more_requests_than_slots():
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    p, _ = m.init(jax.random.PRNGKey(1))
    engine = ServeEngine(m, p, max_batch=2, max_seq=24)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6), max_new=4)
            for i in range(5)]           # more requests than batch slots
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained(max_steps=200)
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) >= 4 for r in reqs)
    # the PTT saw both prefill (critical) and decode (non-critical) updates
    assert engine.scheduler.ptt.updates > len(reqs)


def test_engine_lives_on_its_params_device(subproc):
    """Replica placement: an engine whose params are committed to device i
    keeps its batch cache, chunked-prefill caches and tok/pos on device i,
    and serves the same tokens as an engine on the default device."""
    out = subproc("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import get_model
        from repro.serve import Request, ServeEngine
        cfg = get_config("smollm-135m", reduced=True)
        m = get_model(cfg)
        p, _ = m.init(jax.random.PRNGKey(0))
        dev = jax.devices()[1]
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, n) for n in (5, 11)]
        streams = []
        for params in (p, jax.device_put(p, dev)):
            e = ServeEngine(m, params, max_batch=2, max_seq=32,
                            decode_chunk=2, prefill_chunk_tokens=4)
            reqs = [Request(rid=i, prompt=pr, max_new=5)
                    for i, pr in enumerate(prompts)]
            for r in reqs:
                e.submit(r)
            homes = set()
            for _ in range(3):       # rid 1 admitted, then rid 0 decoding
                e.step()
                homes |= {d for x in jax.tree.leaves(
                    (e.cache, [pf.cache for pf in e.prefilling],
                     e._dev_tok, e._dev_pos)) for d in x.devices()}
            assert e.cache is not None and e.prefilling
            e.run_until_drained(max_steps=100)
            streams.append([r.out_tokens for r in reqs])
        assert homes == {dev}, homes
        assert streams[0] == streams[1], streams
        print("OK")
    """, devices=2)
    assert "OK" in out
