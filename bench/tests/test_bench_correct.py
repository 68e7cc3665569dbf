"""``correct`` can fail.  At a size a test run can hold, on the CPU:

* the control, the float32 reference put in the program's place one
  precision lower (float8 operands), reads above the limit on sequences
  the program served, where the program itself reads below it, and the
  run's own checks judge it not correct;
* a program whose RMSNorm epsilon departs from the published one is
  refused before it runs;
* a whole run of the harness, its chip check skipped, comes out not
  correct when the timed path is broken underneath: a token altered where
  it is produced, or a decode step that returns its KV state unchanged.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, driver, loader, weights
from bench.loader import BenchError
from bench.run import program_config, run_cell
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    return loader.load_cell("tiny.tiny-mix", root=root)


def served(cell, n=4, max_new=24):
    """Serve ``n`` requests through the tiny cell's engine."""
    from repro.serve import Request
    config = cell.config
    cfg = program_config(config)
    w = weights.make_on_device(config["published"], config["architecture"],
                               11)
    system = driver.build_system(config, cfg, [w])
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 40 + 50 * i
                                               ).astype(np.int32),
                    max_new=max_new) for i in range(n)]
    for r in reqs:
        system.submit(r)
    for _ in range(1000):
        if system.idle():
            break
        system.step()
    assert all(r.done for r in reqs)
    return w, [(None, r) for r in reqs]


def test_control_reads_above_the_limit_where_the_program_reads_below(cell):
    hf, arch = cell.config["published"], cell.config["architecture"]
    limit = cell.config["check"]["logit_gap_limit"]
    w, pairs = served(cell)
    seq_len = cell.config["engine"]["max_seq"]
    rows = cell.traffic["output"]["max"]
    program = check.widest_gap(check.gap_fn(hf, arch), w, pairs, seq_len,
                               rows)
    control = check.widest_gap(check.gap_fn(hf, arch, control=True), w,
                               pairs, seq_len, rows)
    assert program < limit < control, (program, control)


def test_control_run_is_judged_not_correct(cell):
    out = control.read(cell, 2**31 + 5, 2.0, require_chip=False)
    assert out["correct"] and not out["control_correct"], json.dumps(out)
    gap = out["control_checks"]["logit_gap_max"]
    assert gap["value"] == out["control"] > gap["limit"]


def test_departing_norm_epsilon_is_refused(cell):
    published = dict(cell.config["published"], rms_norm_eps=1e-5)
    with pytest.raises(BenchError, match="rms_norm_eps"):
        program_config(dict(cell.config, published=published))


def run(cell, seed=2**31 + 3):
    return run_cell(cell, seed, 2.0, False, require_chip=False)


def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], json.dumps(res["checks"])
    assert list(res)[-1] == "checks"


def test_altered_token_is_not_correct(cell, monkeypatch):
    import repro.models as models
    orig = models._fused_decode

    def altered(cfg, mod):
        fused = orig(cfg, mod)

        def call(params, token, pos, cache, k):
            toks, nxt, pos, cache = fused(params, token, pos, cache, k)
            return (toks + 1) % cfg.vocab, nxt, pos, cache
        return call

    monkeypatch.setattr(models, "_fused_decode", altered)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > \
        res["checks"]["logit_gap_max"]["limit"]


def test_decode_that_keeps_its_state_is_not_correct(cell, monkeypatch):
    from repro.models import transformer
    orig = transformer.decode

    def stale(cfg, p, token, pos, cache):
        logits, _ = orig(cfg, p, token, pos, cache)
        return logits, cache            # the new K/V rows are dropped

    monkeypatch.setattr(transformer, "decode", stale)
    res = run(cell)
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > \
        res["checks"]["logit_gap_max"]["limit"]
