#!/usr/bin/env python3
"""Find the knee of a cell: the highest offered rate at which completions
keep up with arrivals and the queue (requests submitted that have no first
token yet) does not grow over the window.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,2 [--seconds 40]
                           [--replicas 1] [--seed 0]

One process, one set-up: the cell's engines are built and warmed once,
then each rate plays the cell's mix (same sizes, same shape of gaps,
scaled to that rate) for ``steady_s`` plus ``--seconds``, drains, and
prints one JSON line.  ``--replicas 1`` sweeps a single engine of a fleet
configuration on the first chip, as a reference for the fleet's knee.
The knee is read from the lines: it is written into the traffic file by
hand, as a number, with the sweep beside it in ``PERF.md``.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--replicas", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench import driver, loader, stats, traffic, weights
    from bench.run import enable_cache, program_config, seed31
    cell = loader.load_cell(args.workload)
    config, mix = dict(cell.config), cell.traffic
    if args.replicas:
        config["replicas"] = args.replicas
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < config["replicas"]:
        print(f"sweep: needs {config['replicas']} TPU chips", file=sys.stderr)
        return 2
    enable_cache()
    cfg = program_config(config, cell.arch)
    w = weights.make_on_device(cell.arch, config, seed31(args.seed, 3),
                               devs[0])
    params = [w] + [jax.device_put(w, d)
                    for d in devs[1:config["replicas"]]]
    system = driver.build_system(config, cfg, params)
    driver.warm_up(system, config, cfg.vocab)
    steady, drain = float(mix["steady_s"]), 30.0
    for rate in [float(r) for r in args.rates.split(",")]:
        arrivals = traffic.schedule(mix, steady + args.seconds,
                                    rate_per_s=rate)
        loop = driver.OpenLoop(system, arrivals, args.seed, cfg.vocab)
        waiting = []

        def sample():
            # the queue: submitted, no first token yet (decoding requests
            # are served work, not backlog)
            waiting.append(sum(loop.requests[i].t_first is None
                               for i in loop.live))

        quarter = args.seconds / 4
        hooks = {"open": sample,
                 "at": [(quarter * i, sample) for i in (1, 2, 3)],
                 "close": sample}
        win = loop.run(steady, args.seconds, drain, hooks)
        e2e = stats.end_to_end(loop.records, win.w_open, win.w_close,
                               win.t_end, win.tokens_close - win.tokens_open,
                               mix["limits"])
        due = [r for r in loop.records if win.w_open <= r.due < win.w_close]
        done_in_window = sum(r.t_done is not None and r.t_done < win.w_close
                             for r in due)
        ttfts = [r.ttft(win.t_end) for r in due]
        tpots = [t for t in (r.tpot() for r in due) if t is not None]
        print(json.dumps({
            "rate_per_s": rate, "due_in_window": len(due),
            "finished_in_window": done_in_window,
            "waiting_at_quarters": waiting,
            "drained": win.drained, "drain_s": win.t_end - win.w_close,
            "ttft_p50_ms": stats.percentile(ttfts, 50) * 1e3,
            "ttft_p95_ms": e2e["ttft_p95_ms"],
            "tpot_p50_ms": stats.percentile(tpots, 50) * 1e3,
            "tpot_p95_ms": e2e["tpot_p95_ms"],
            "tokens_per_s": e2e["tokens_per_s"],
            "failed": e2e["failed"],
            "lateness_p99_ms": float(np.percentile(loop.lateness, 99)) * 1e3,
        }), flush=True)
        # let the engines empty before the next rate
        t_stop = time.perf_counter() + 60
        while not system.idle() and time.perf_counter() < t_stop:
            system.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
