#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

In order: load the cell's configuration and traffic mix (by the names in
``BENCHMARK.json``), make the weights on the device from the seed, build
the engines (and the gateway) as the configuration states, warm up every
program the traffic drives, play ``steady_s`` seconds of the open-loop
traffic, measure for ``--seconds``, stop arrivals and drain, then check
what was served against the float32 reference of the configuration's
architecture file.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of a few seconds of the window and reports the per-layer
metrics instead.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``busy_s``/``window_s`` and a ``breakdown`` when traced) and ``checks``,
each number compared beside its limit.  The same checks are the last
lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# libtpu otherwise keeps its logs in a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_AT_S = 5.0         # profiler starts this far into the window
TRACE_S = 3.0            # and records this long
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    pass


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def seed31(seed: int, stream: int) -> int:
    import numpy as np
    return int(np.random.default_rng([seed % 2**64, stream]).integers(2**31))


def enable_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    pathlib.Path(d).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def program_norm_eps(cfg) -> float:
    """The epsilon the program's RMSNorm adds to the mean square, read off
    the norm itself on a row whose mean square is 1e-6."""
    import jax.numpy as jnp
    from repro.models.layers import apply_norm
    a = 1e-3
    out = apply_norm({"scale": jnp.ones((8,), jnp.float32)},
                     jnp.full((1, 8), a, jnp.float32), cfg.norm)
    return (a / float(out[0, 0])) ** 2 - a * a


def program_config(config: dict, arch):
    """The program's model configuration, held to what the architecture
    file ``arch`` reads off the configuration (``program_want``), to its
    weight type and to the published RMSNorm epsilon."""
    import dataclasses
    import math
    from repro.configs import get_config
    from bench.loader import BenchError
    from bench.weights import param_dtype
    cfg = get_config(config["model"])
    if config.get("model_overrides"):
        cfg = dataclasses.replace(cfg, **config["model_overrides"])
    hf = config["published"]
    want = dict(arch.program_want(hf, config["architecture"]),
                param_dtype=param_dtype(config))
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    eps = program_norm_eps(cfg)
    if not math.isclose(eps, hf["rms_norm_eps"], rel_tol=0.01):
        bad["rms_norm_eps"] = (eps, hf["rms_norm_eps"])
    if bad:
        raise BenchError(f"program config departs from the file: {bad}")
    return cfg


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_start: float = T_START,
             on_check=None) -> dict:
    """One run of ``cell``; returns the result object (see the module
    docstring).  ``require_chip=False`` lets tests drive the whole run on
    the CPU at a tiny size, without the persistent compilation cache.
    ``on_check(weights, sampled pairs)``, if given, runs after the
    comparison, with the reference's inputs (the control uses it)."""
    import jax
    import numpy as np
    from bench import (check, counting, driver, instrument, stats, traffic,
                       weights)
    from bench.compile_meter import CompileMeter, GcMeter
    from bench.record import RunRecord

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chips; JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    if require_chip:
        log(f"compile cache: {enable_cache()}")
    meter = CompileMeter()
    config, mix = cell.config, cell.traffic
    cfg = program_config(config, cell.arch)
    dims = cell.arch.dims(config["published"])
    replicas = int(config["replicas"])
    if replicas > len(devs):
        raise NoChip(f"{replicas} replicas need {replicas} devices")

    w = weights.make_on_device(cell.arch, config, seed31(seed, 3), devs[0])
    params = [w] + [jax.device_put(w, d) for d in devs[1:replicas]]
    system = driver.build_system(config, cfg, params)
    driver.warm_up(system, config, cfg.vocab)
    log(f"warm-up done at {time.perf_counter() - t_start:.2f}s: {meter}")
    # A full collection walks every object the imports, the traced
    # programs and the engines left behind; inside the window it stalls
    # the serving loop.  Collect once here, in set-up, and move what is
    # left out of the collector's sight: later collections walk only what
    # the traffic makes.
    t_gc, n_obj = time.perf_counter(), len(gc.get_objects())
    gc.collect()
    gc.freeze()
    log(f"heap: full collection over {n_obj} objects took "
        f"{(time.perf_counter() - t_gc) * 1e3:.1f}ms; "
        f"{gc.get_freeze_count()} objects frozen")
    gcm = GcMeter()

    steady, drain = float(mix["steady_s"]), float(mix["drain_s"])
    arrivals = traffic.schedule(mix, steady + seconds)
    loop = driver.OpenLoop(system, arrivals, seed, cfg.vocab,
                           annotate=trace)
    steplog = instrument.StepLog(system.engines) if trace else None
    marks = {}

    def opened():
        marks["compiles_open"] = meter.compiles
        marks["gc_open"] = gcm.collections
        gcm.longest_s = 0.0

    def closed():
        marks["compiles_close"] = meter.compiles
        marks["gc_close"] = gcm.collections
        marks["gc_longest_ms"] = gcm.longest_s * 1e3

    hooks = {"open": opened, "close": closed}
    tracer = None
    if trace:
        from repro.obs import NULL_TRACER, SpanTracer
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = SpanTracer("bench")
        ann = {}

        def start():
            jax.profiler.start_trace(str(TRACE_DIR))
            for i, e in enumerate(system.engines):
                e.attach_obs(tracer=tracer, name=f"e{i}")
            ann["span"] = jax.profiler.TraceAnnotation("bench.trace")
            ann["span"].__enter__()
            marks["traced"] = [time.perf_counter()]

        def stop():
            marks["traced"].append(time.perf_counter())
            ann["span"].__exit__(None, None, None)
            for e in system.engines:
                e.attach_obs(tracer=NULL_TRACER)
            jax.profiler.stop_trace()

        at = min(TRACE_AT_S, seconds / 4)
        hooks["at"] = [(at, start), (at + min(TRACE_S, seconds / 2), stop)]

    win = loop.run(steady, seconds, drain, hooks)
    setup_s = win.w_open - t_start
    compiles_in_window = marks["compiles_close"] - marks["compiles_open"]
    lateness = np.asarray(loop.lateness) * 1e3
    log(f"generator lateness over {lateness.size} submissions: p50="
        f"{np.percentile(lateness, 50):.3f}ms p99="
        f"{np.percentile(lateness, 99):.3f}ms max={lateness.max():.3f}ms")
    log(f"compiles inside the window: {compiles_in_window}; {meter}")
    log(f"full collections inside the window: "
        f"{marks['gc_close'] - marks['gc_open']}, longest "
        f"{marks['gc_longest_ms']:.1f}ms")
    gcm.close()
    log(f"drained={win.drained} in {win.t_end - win.w_close:.2f}s")
    device = device_info(jax, max(replicas, cell.chips))

    e2e = stats.end_to_end(loop.records, win.w_open, win.w_close, win.t_end,
                           win.tokens_close - win.tokens_open, mix["limits"])
    due = [r for r in loop.records if win.w_open <= r.due < win.w_close]
    ttfts = [r.ttft(win.t_end) * 1e3 for r in due]
    tpots = [r.tpot() * 1e3 for r in due if r.tpot() is not None]
    log(f"requests due in the window: {len(due)}; ttft_ms p50/p90/p95="
        f"{stats.percentile(ttfts, 50):.1f}/{stats.percentile(ttfts, 90):.1f}"
        f"/{stats.percentile(ttfts, 95):.1f}; tpot_ms p50/p90/p95="
        f"{stats.percentile(tpots, 50):.2f}/{stats.percentile(tpots, 90):.2f}"
        f"/{stats.percentile(tpots, 95):.2f}")
    result = {"correct": False, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": device}
    if trace:
        from bench import trace_reduce
        if len(marks.get("traced", [])) != 2:
            raise RuntimeError("the profiler window did not close inside "
                               "the measured window")
        path = next(TRACE_DIR.rglob("*.xplane.pb"))
        tr = trace_reduce.load(str(path))
        span = tr.span("bench.trace")
        # off the chip (tests only) the table's v5e entry stands in
        kind = devs[0].device_kind if require_chip else "TPU v5 lite"
        rec = RunRecord(dims=dims, peaks=counting.peaks_for(kind),
                        replicas=replicas,
                        records=loop.records, window=win,
                        steps=steplog.steps,
                        prefill_chunks=instrument.prefill_chunks(
                            tracer, *marks["traced"]),
                        trace=tr, traced=tuple(marks["traced"]),
                        traced_ns=span)
        used = rec.devices()
        t0, t1 = span
        busy = [trace_reduce.covered(d.busy(t0, t1)) for d in used]
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = (t1 - t0) / 1e9
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        if used:
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(used, t0, t1),
                "idle_gaps": trace_reduce.idle_gaps(used[0], tr.host, t0, t1)}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}

    # correctness, once the program's state is freed
    finished = [(r, loop.requests[r.idx]) for r in loop.records
                if win.w_open <= r.due < win.w_close and not r.failed]
    wrong_count = sum(len(q.out_tokens) != r.max_new for r, q in finished)
    ck = config["check"]
    pairs = check.sample(finished, ck["sample"], seed, replicas > 1)
    del system, loop, params, steplog
    gc.unfreeze()
    gc.collect()
    eng = config["engine"]
    t_check = time.perf_counter()
    fn = check.gap_fn(cell.arch, config)
    gap = check.widest_gap(fn, w, pairs, eng["max_seq"],
                           mix["output"]["max"])
    served = sum(len(q.out_tokens) for _, q in pairs)
    log(f"reference check of {len(pairs)} requests, {served} served tokens: "
        f"{time.perf_counter() - t_check:.2f}s")
    if on_check is not None:
        on_check(w, pairs)
    checks = {
        "logit_gap_max": {"value": gap, "limit": ck["logit_gap_limit"]},
        "served_tokens_checked": {"value": served,
                                  "limit": ck["min_tokens_checked"]},
        "finished_with_wrong_count": {"value": wrong_count, "limit": 0},
    }
    if replicas > 1:
        checks["replicas_checked"] = {
            "value": len({r.replica for r, _ in pairs}), "limit": replicas}
    result["correct"] = judge(checks)
    result["checks"] = checks
    return result


# checks that hold when the value reaches the limit; every other check
# holds while the value stays at or under it
AT_LEAST = ("served_tokens_checked", "replicas_checked")


def judge(checks: dict) -> bool:
    """Whether every number compared lies on the right side of its limit."""
    return all(c["value"] >= c["limit"] if k in AT_LEAST
               else c["value"] <= c["limit"] for k, c in checks.items())


def describe_checks(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']} limit {v['limit']}"
            for k, v in checks.items()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import loader
    try:
        cell = loader.load_cell(args.workload)
        import repro  # noqa: F401  the system under test
    except (ImportError, loader.BenchError) as e:
        log(f"bench: cannot load the cell or the program: {e}")
        return 2
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    for line in describe_checks(result["checks"]):
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
