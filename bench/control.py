#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's widest logit gap and
the control's, on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

For each seed this runs the cell as ``bench/run.py`` does (its traffic at
its own rate, a short window) and prints one JSON line: the program's
reading, ``logit_gap_max``, and its verdict ``correct``; then the
control's reading on the same sampled sequences (the float32 reference
computed with float8 operands, judged at each position by the gap of the
token it puts first) and the verdict the same checks and limits give it,
``control_correct``, which has to come out false.  The limit in the
configuration's ``check`` must lie above the largest program reading and
below the smallest control reading (``PERF.md`` gives both).  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def read(cell, seed: int, seconds: float, require_chip: bool = True) -> dict:
    """One run of ``cell`` with the control read on its sampled requests,
    both judged by the run's own checks and limits."""
    from bench import check
    from bench.run import judge, run_cell
    control_fn = check.gap_fn(cell.arch, cell.config, control=True)
    got = {}

    def on_check(w, pairs):
        got["gap"] = check.widest_gap(
            control_fn, w, pairs, cell.config["engine"]["max_seq"],
            cell.traffic["output"]["max"])

    res = run_cell(cell, seed, seconds, False, require_chip=require_chip,
                   t_start=time.perf_counter(), on_check=on_check)
    limit = res["checks"]["logit_gap_max"]["limit"]
    control = dict(res["checks"],
                   logit_gap_max={"value": got["gap"], "limit": limit})
    return {"seed": seed, "correct": res["correct"],
            "program": res["checks"]["logit_gap_max"]["value"],
            "control": got["gap"], "control_correct": judge(control),
            "checks": res["checks"], "control_checks": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import loader
    from bench.run import NoChip
    cell = loader.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            out = read(cell, seed, args.seconds)
        except NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
