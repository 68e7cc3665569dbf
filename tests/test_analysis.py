"""Acceptance for the repro.analysis suite (lint / jaxpr audit /
contracts): each rule catches its broken fixture, annotated or guarded
sites stay clean, the CLI's JSON report is pinned to a golden file, the
donation audit fails when donation is dropped, and the real tree is
finding-free.

Regenerate the golden report after an intentional rule/format change with

    PYTHONPATH=src python tests/test_analysis.py --regen
"""

import io
import contextlib
import json
import os
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import Baseline, Finding
from repro.analysis.cli import main as analysis_main
from repro.analysis.findings import sort_findings
from repro.analysis.jaxpr_audit import (audit_decode_fused,
                                        audit_prefill_chunk,
                                        cache_leaf_names, donation_findings,
                                        jaxpr_findings)
from repro.analysis.lint import (lint_bare_retry, lint_hot_path,
                                 lint_metric_cardinality, lint_wall_clock,
                                 lint_wire_compat, run_lint)

HERE = os.path.dirname(__file__)
REPO_ROOT = os.path.abspath(os.path.join(HERE, ".."))
FIXTURE_ROOT = os.path.join(HERE, "fixtures", "analysis")
GOLDEN = os.path.join(HERE, "golden", "analysis_findings.json")


def _cli(argv) -> tuple:
    """(exit_code, stdout) of one in-process CLI invocation."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = analysis_main(argv)
    return rc, buf.getvalue()


def _fixture_report() -> str:
    rc, out = _cli(["--only", "lint", "--root", FIXTURE_ROOT,
                    "--format", "json"])
    assert rc == 1, "broken fixture tree must gate non-zero"
    return out


# ---------------------------------------------------------------------------
# golden CLI report over the broken fixture tree
# ---------------------------------------------------------------------------

def test_fixture_report_matches_golden():
    with open(GOLDEN) as f:
        assert _fixture_report() == f.read()


def test_fixture_report_covers_every_rule():
    report = json.loads(_fixture_report())
    rules = {f["rule"] for f in report["findings"]}
    assert rules == {"hot-path-host-sync", "unguarded-span",
                     "wall-clock-latency", "wire-compat", "kernel-triad",
                     "bare-retry", "metric-cardinality", "parse-error"}
    assert report["counts"]["new"] == len(report["findings"])
    # the complete triad with a force_pallas kwarg stays finding-free
    assert not any("goodkernel" in f["path"] or "goodkernel" in f["message"]
                   for f in report["findings"])


# ---------------------------------------------------------------------------
# per-rule units: the guarded/annotated twin of each fixture stays clean
# ---------------------------------------------------------------------------

_HOT = textwrap.dedent("""\
    import numpy as np

    class ServeEngine:
        def step(self):
            toks = self._chunk()
            %s
            return toks

        def _chunk(self):
            return [1]
    """)


def test_hot_path_sync_annotation():
    bad = lint_hot_path(_HOT % "out = np.asarray(toks)", "engine.py")
    assert [f.rule for f in bad] == ["hot-path-host-sync"]
    assert bad[0].line == 6
    ok = lint_hot_path(
        _HOT % "out = np.asarray(toks)  # analysis: allow-host-sync(chunk boundary)",
        "engine.py")
    assert ok == []


def test_hot_path_only_flags_reachable_functions():
    # same sync in a method NOT reachable from the seeds: clean
    src = _HOT % "pass"
    src += "    def offline_dump(self):\n        return np.asarray([1])\n"
    assert lint_hot_path(src, "engine.py") == []


def test_unguarded_span_rule():
    guarded = _HOT % ("if self.tracer.enabled:\n"
                      "            self.tracer.instant('x', 1)")
    assert lint_hot_path(guarded, "engine.py") == []
    unguarded = _HOT % "self.tracer.instant('x', 1)"
    fs = lint_hot_path(unguarded, "engine.py")
    assert [f.rule for f in fs] == ["unguarded-span"]
    assert fs[0].severity == "warning"


def test_wall_clock_rule():
    src = "import time\nd = time.time()\n"
    fs = lint_wall_clock(src, "x.py")
    assert [f.rule for f in fs] == ["wall-clock-latency"]
    ok = "import time\nd = time.perf_counter()\nm = time.monotonic()\n"
    assert lint_wall_clock(ok, "x.py") == []


def test_bare_retry_rule():
    bad = textwrap.dedent("""\
        while True:
            try:
                ship()
            except IOError:
                continue
        """)
    fs = lint_bare_retry(bad, "x.py")
    assert [f.rule for f in fs] == ["bare-retry"]
    assert fs[0].severity == "warning"
    # geometric backoff + exhaustion raise: disciplined, clean
    ok = textwrap.dedent("""\
        delay = 0.1
        while True:
            try:
                ship()
            except IOError:
                if delay > 2.0:
                    raise
                delay *= 2
                continue
        """)
    assert lint_bare_retry(ok, "x.py") == []
    # a for-range loop is structurally capped: never flagged
    capped = textwrap.dedent("""\
        for _ in range(3):
            try:
                ship()
            except IOError:
                continue
        """)
    assert lint_bare_retry(capped, "x.py") == []
    # the annotation escape hatch
    allowed = textwrap.dedent("""\
        while True:
            try:
                ship()
            except IOError:
                # analysis: allow-bare-retry(busy-wait on local queue)
                continue
        """)
    assert lint_bare_retry(allowed, "x.py") == []


def test_metric_cardinality_rule():
    bad = textwrap.dedent("""\
        def attach(metrics, req):
            metrics.counter(f"requests_{req.rid}_total", "per request")
            metrics.gauge("tokens", "t", session_id=str(req.session_id))
            metrics.histogram("lat_seconds", "l", rid=req.rid)
            self.registry.counter("x_total", "x", key="a" + req.user)
        """)
    fs = lint_metric_cardinality(bad, "x.py")
    assert [f.rule for f in fs] == ["metric-cardinality"] * 4
    assert [f.line for f in fs] == [2, 3, 4, 5]
    assert all(f.severity == "warning" for f in fs)
    # bounded-dimension labels from plain variables are the normal idiom
    ok = textwrap.dedent("""\
        def attach(metrics, g):
            for r in range(n):
                metrics.gauge("drift_ratio", "d", fleet=g, replica=r)
            metrics.counter("served_total", "s", fleet=g, state="firing")
        """)
    assert lint_metric_cardinality(ok, "x.py") == []
    # only registry-ish receivers are in scope: a tracer instant may
    # carry ids freely (spans are bounded deques)
    tracer = 'tracer.counter = 1\nx.instant("n", rid=str(req.rid))\n'
    assert lint_metric_cardinality(tracer, "x.py") == []
    # the annotation escape hatch
    allowed = textwrap.dedent("""\
        def attach(metrics, req):
            metrics.counter(  # analysis: allow-metric-cardinality(capped)
                f"debug_{req.phase}_total", "phase is a 3-value enum")
        """)
    assert lint_metric_cardinality(allowed, "x.py") == []


def test_wire_compat_rule():
    ok = "WIRE_VERSION = 3\nWIRE_COMPAT = frozenset({1, 2, 3})\n"
    assert lint_wire_compat(ok, "wire.py") == []
    bumped = "WIRE_VERSION = 4\nWIRE_COMPAT = frozenset({1, 2, 3})\n"
    fs = lint_wire_compat(bumped, "wire.py")
    assert [f.rule for f in fs] == ["wire-compat"]
    orphan = "WIRE_VERSION = 4\n"
    assert [f.rule for f in lint_wire_compat(orphan, "wire.py")] == [
        "wire-compat"]


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

def _finding(msg="m"):
    return Finding("wall-clock-latency", "warning", "a.py", 7, msg)


def test_baseline_roundtrip(tmp_path):
    base = Baseline.from_findings([_finding()], reason="legacy launcher")
    p = tmp_path / "analysis_baseline.json"
    base.dump(p)
    loaded = Baseline.load(p)
    new, suppressed = loaded.apply([_finding(), _finding("other")])
    assert [f.message for f in new] == ["other"]
    assert [f.message for f in suppressed] == ["m"]
    # line moves never resurrect a suppressed finding
    moved = Finding("wall-clock-latency", "warning", "a.py", 99, "m")
    assert loaded.matches(moved)


def test_baseline_requires_reason(tmp_path):
    with pytest.raises(ValueError, match="reason"):
        Baseline([{"rule": "x", "path": "a.py"}])
    rc, _ = _cli(["--only", "lint", "--root", FIXTURE_ROOT,
                  "--write-baseline"])
    assert rc == 2                       # --write-baseline without --reason


def test_write_baseline_then_clean(tmp_path):
    bp = str(tmp_path / "analysis_baseline.json")
    rc, _ = _cli(["--only", "lint", "--root", FIXTURE_ROOT,
                  "--baseline", bp, "--write-baseline",
                  "--reason", "fixture adoption"])
    assert rc == 0
    rc, out = _cli(["--only", "lint", "--root", FIXTURE_ROOT,
                    "--baseline", bp, "--format", "json"])
    assert rc == 0                       # everything baselined -> gate green
    report = json.loads(out)
    assert report["counts"]["new"] == 0
    assert report["counts"]["baselined"] > 0


# ---------------------------------------------------------------------------
# jaxpr audit: donation, callbacks, f64
# ---------------------------------------------------------------------------

def _toy_cache():
    return {"k": jnp.zeros((2, 4, 8), jnp.float32),
            "v": jnp.zeros((2, 4, 8), jnp.float32)}


def _toy_decode(params, tok, pos, cache):
    new = {n: c + tok.astype(c.dtype).sum() for n, c in cache.items()}
    return tok + 1, new


def test_donation_audit_fails_when_donation_dropped():
    """THE regression the audit exists for: same program, donation dropped
    -> every cache leaf flagged; donated -> clean."""
    args = (jnp.zeros((2,), jnp.float32), jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), _toy_cache())
    leaves = cache_leaf_names(args[3])
    donated = jax.jit(_toy_decode, donate_argnums=3).lower(*args).as_text()
    assert donation_findings(donated, leaves, "toy") == []
    dropped = jax.jit(_toy_decode).lower(*args).as_text()
    fs = donation_findings(dropped, leaves, "toy")
    assert [f.rule for f in fs] == ["dropped-donation", "dropped-donation"]
    assert {f.severity for f in fs} == {"error"}
    assert any("['k']" in f.message for f in fs)


def test_donation_audit_survives_pruned_args():
    """jit prunes unused arguments from the lowering, shifting argument
    numbering — the audit must match donated leaves by type, not index
    (this is exactly how the vlm family lowers: two unused param leaves)."""
    def fn(unused_a, unused_b, tok, cache):
        return tok, {n: c + 1.0 for n, c in cache.items()}
    args = (jnp.zeros((64, 64)), jnp.zeros((128,)),
            jnp.zeros((2, 1), jnp.int32), _toy_cache())
    text = jax.jit(fn, donate_argnums=3).lower(*args).as_text()
    assert donation_findings(text, cache_leaf_names(args[3]), "toy") == []


def test_jaxpr_flags_host_callback_and_f64():
    def chatty(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2
    jaxpr = jax.make_jaxpr(chatty)(jnp.ones((2,), jnp.float32))
    rules = [f.rule for f in jaxpr_findings(jaxpr.jaxpr, "toy")]
    assert rules == ["host-callback"]

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2)(
            jnp.ones((2,), jnp.float32))
    rules = [f.rule for f in jaxpr_findings(jaxpr.jaxpr, "toy")]
    assert rules == ["f64-promotion"]

    clean = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((2,), jnp.float32))
    assert jaxpr_findings(clean.jaxpr, "toy") == []


def test_decode_fused_donation_clean_for_dense_family():
    """End-to-end: the real dense fast path keeps every KV leaf aliased
    (the other four families are covered by the CI analysis job)."""
    assert audit_decode_fused("qwen2-0.5b") == []
    assert audit_prefill_chunk("qwen2-0.5b") == []


# ---------------------------------------------------------------------------
# the merged tree is finding-free
# ---------------------------------------------------------------------------

def test_clean_tree_lint_and_contracts():
    rc, out = _cli(["--only", "lint,contracts", "--root", REPO_ROOT,
                    "--format", "json"])
    report = json.loads(out)
    assert rc == 0, report["findings"]
    assert report["counts"]["new"] == 0


def test_clean_tree_lint_findings_list_is_empty():
    # run_lint directly (no baseline): the tree itself carries zero
    # violations, the gate isn't leaning on suppressions
    assert sort_findings(run_lint(REPO_ROOT)) == []


# ---------------------------------------------------------------------------
# golden regeneration
# ---------------------------------------------------------------------------

if __name__ == "__main__" and "--regen" in sys.argv:
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w") as f:
        f.write(_fixture_report())
    print(f"regenerated {GOLDEN}")
