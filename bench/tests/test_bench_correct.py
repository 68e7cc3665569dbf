"""``correct`` can fail.  At a size a test run can hold, on the CPU:

* the control, the float32 reference put in the program's place one
  precision lower (float8 operands), reads above the limit on sequences
  the program served, where the program itself reads below it, and the
  run's own checks judge it not correct;
* a program whose RMSNorm epsilon or weight type departs from the
  configuration is refused before it runs;
* weights stored in bfloat16, and a second architecture dropped into a
  throwaway root as one file, are served and pass the same comparison;
* a whole run of the harness, its chip check skipped, comes out not
  correct when the timed path is broken underneath: a token altered where
  it is produced, or a decode step that returns its KV state unchanged.
"""

import json
import pathlib
import shutil

import jax
import numpy as np
import pytest

from bench import check, control, counting, driver, loader, weights
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
    cfg = program_config(config, cell.arch)
    w = weights.make_on_device(cell.arch, config, 11)
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


def gaps(cell, w, pairs):
    """The program's widest gap on ``pairs`` and the control's."""
    seq_len = cell.config["engine"]["max_seq"]
    rows = cell.traffic["output"]["max"]
    return [check.widest_gap(check.gap_fn(cell.arch, cell.config, c), w,
                             pairs, seq_len, rows) for c in (False, True)]


def test_control_reads_above_the_limit_where_the_program_reads_below(cell):
    limit = cell.config["check"]["logit_gap_limit"]
    w, pairs = served(cell)
    program, control = gaps(cell, w, pairs)
    assert program < limit < control, (program, control)


def test_control_run_is_judged_not_correct(cell):
    out = control.read(cell, 2**31 + 5, 2.0, require_chip=False)
    assert out["correct"] and not out["control_correct"], json.dumps(out)
    gap = out["control_checks"]["logit_gap_max"]
    assert gap["value"] == out["control"] > gap["limit"]


def test_departing_norm_epsilon_is_refused(cell):
    published = dict(cell.config["published"], rms_norm_eps=1e-5)
    with pytest.raises(BenchError, match="rms_norm_eps"):
        program_config(dict(cell.config, published=published), cell.arch)


def test_departing_param_dtype_is_refused(cell):
    settings = dict(cell.config["architecture"], param_dtype="bfloat16")
    with pytest.raises(BenchError, match="param_dtype"):
        program_config(dict(cell.config, architecture=settings), cell.arch)


def test_bfloat16_weights_pass_the_reference_gap(tmp_path):
    config = dict(tiny.CONFIG,
                  architecture=dict(tiny.CONFIG["architecture"],
                                    param_dtype="bfloat16"),
                  model_overrides=dict(tiny.CONFIG["model_overrides"],
                                       param_dtype="bfloat16"))
    cell = loader.load_cell("tiny.tiny-mix",
                            root=tiny.make_root(tmp_path, config))
    w, pairs = served(cell)
    assert {str(a.dtype) for a in jax.tree.leaves(w)} == {"bfloat16"}
    program, control = gaps(cell, w, pairs)
    assert program < config["check"]["logit_gap_limit"] < control, \
        (program, control)


UNTIED = pathlib.Path(__file__).resolve().parent / "arch_untied.py"


def test_a_new_architecture_is_one_file(tmp_path):
    """A configuration naming ``untied``, a file the repository's
    ``bench/archs/`` does not have, in a root that differs from the
    repository's only by that file: it is loaded, the program is held to
    it, served with its weights, checked against its reference and counted
    through its ``dims``."""
    published = dict(tiny.PUBLISHED, tie_word_embeddings=False)
    config = dict(tiny.CONFIG, arch="untied", published=published,
                  model_overrides=dict(tiny.CONFIG["model_overrides"],
                                       tie_embeddings=False))
    root = tiny.make_root(tmp_path, config)
    shutil.copy(UNTIED, root / "bench" / "archs" / "untied.py")
    cell = loader.load_cell("tiny.tiny-mix", root=root)
    assert pathlib.Path(cell.arch.__file__) == \
        root / "bench" / "archs" / "untied.py"
    cfg = program_config(cell.config, cell.arch)
    assert not cfg.tie_embeddings
    with pytest.raises(BenchError, match="tie_embeddings"):
        program_config(dict(cell.config, model_overrides=tiny.CONFIG[
            "model_overrides"]), cell.arch)
    w, pairs = served(cell)
    assert w["tok"]["lm_head"].shape == (64, 512)
    program, control = gaps(cell, w, pairs)
    limit = cell.config["check"]["logit_gap_limit"]
    assert program < limit < control, (program, control)
    d = cell.arch.dims(published)
    # per layer: QKV 64 * (4 + 2 * 2) * 16, output 64 * 64, MLP 3 * 64 * 128
    per_layer = 64 * 8 * 16 + 64 * 64 + 3 * 64 * 128
    assert d.matmul_flops_per_token == 2 * 2 * per_layer
    assert counting.decode_model_flops(d, [10]) == \
        2 * 2 * per_layer + 2 * 64 * 512 + 4 * 4 * 16 * 10 * 2


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
