#!/usr/bin/env python3
"""What the program's span tracer costs on the chip, and where the time of
a long step, or of a long gap between steps, went.

    python3 bench/tracing_cost.py --workload <cell> --seed <n>
                                  --seconds <s> --tracer <0|1>

Runs the cell as ``bench/run.py --trace 0`` does, with no profiler, and
times every ``step`` call of every engine.  With ``--tracer 1`` a
``repro.obs.SpanTracer`` is attached to every engine for the whole of the
traffic, as an operator would leave it on, so each step also records its
``engine.*`` phase spans.  Compare the two on the same seeds.

The last line of standard output is one JSON object: the run's end-to-end
metrics, ``correct`` and its checks; the count, mean and median of the
step times in the measured window; with the tracer, the mean time per
phase; and every step, or gap between one replica's steps, longer than
``--long`` seconds, with the phases seen inside it.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _phases(tracer, track: str, t0: float, t1: float) -> dict:
    """Milliseconds per phase span on ``track`` inside [t0, t1]."""
    out: dict[str, float] = {}
    if tracer is None:
        return out
    for ev in tracer.events:
        if (ev["track"] == track and ev["ph"] == "X"
                and ev["name"].startswith("engine.")
                and ev["name"] != "engine.step"
                and t0 <= ev["ts"] and ev["ts"] + ev["dur"] <= t1):
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] * 1e3
    return out


def measure(cell, seed: int, seconds: float, tracer: bool,
            long_s: float = 0.5, require_chip: bool = True) -> dict:
    from bench import driver, instrument
    from bench.run import run_cell
    tr = None
    if tracer:
        from repro.obs import SpanTracer
        tr = SpanTracer("cost")
    marks = {}
    play = driver.OpenLoop.run

    def run(loop, *a, **kw):
        marks["log"] = instrument.StepLog(loop.system.engines)
        if tr is not None:
            for i, e in enumerate(loop.system.engines):
                e.attach_obs(tracer=tr, name=f"e{i}")
        marks["win"] = play(loop, *a, **kw)
        return marks["win"]

    driver.OpenLoop.run = run
    try:
        res = run_cell(cell, seed, seconds, False, require_chip=require_chip)
    finally:
        driver.OpenLoop.run = play
    win = marks["win"]
    steps = [(s.replica, s.t0, s.t1) for s in marks["log"].steps]
    inside = [s for s in steps if s[1] >= win.w_open and s[2] <= win.w_close]
    ms = [(t1 - t0) * 1e3 for _, t0, t1 in inside]
    out = {"tracer": tracer, "seed": seed, "correct": res["correct"],
           "attempted": res["attempted"], "failed": res["failed"],
           "checks": res["checks"],
           "metrics": {k: v["value"] for k, v in res["metrics"].items()},
           "steps": len(ms),
           "step_ms_mean": statistics.fmean(ms) if ms else None,
           "step_ms_p50": statistics.median(ms) if ms else None}
    if tr is not None and inside:
        per: dict[str, float] = {}
        for i, t0, t1 in inside:
            for k, v in _phases(tr, f"e{i}", t0, t1).items():
                per[k] = per.get(k, 0.0) + v
        out["phase_ms_mean"] = {k: v / len(inside) for k, v in per.items()}
    long = []
    last: dict[int, float] = {}
    for i, t0, t1 in sorted(steps, key=lambda s: s[1]):
        gap0 = last.get(i)
        last[i] = t1
        if t0 < win.w_open or t1 > win.w_close:
            continue
        if t1 - t0 > long_s:
            long.append({"kind": "step", "replica": i,
                         "at_s": t0 - win.w_open, "ms": (t1 - t0) * 1e3,
                         "phases": _phases(tr, f"e{i}", t0, t1)})
        if gap0 is not None and t0 - gap0 > long_s:
            long.append({"kind": "gap", "replica": i,
                         "at_s": gap0 - win.w_open, "ms": (t0 - gap0) * 1e3})
    out["long"] = long
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), required=True)
    ap.add_argument("--long", type=float, default=0.5)
    args = ap.parse_args(argv)
    from bench import loader
    from bench.run import NoChip
    try:
        out = measure(loader.load_cell(args.workload), args.seed,
                      args.seconds, bool(args.tracer), args.long)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
