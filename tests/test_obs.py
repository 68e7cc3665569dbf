"""Telemetry plane acceptance: metric registry exposition (golden-file),
Chrome trace-event export (golden-file + schema), decision-attribution
additivity at the TraceTable and in the fleet benchmark, the unified
``stats()`` counter names across all three scales, and the headline
span-tracer property — a live-migrated request keeps ONE causal timeline
spanning both replicas.

Regenerate the golden fixtures after an intentional format change with

    PYTHONPATH=src python tests/test_obs.py --regen
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

from benchmarks.common import percentile  # noqa: E402
from repro.core.tracetable import (Candidate, Latency, Occupancy,  # noqa: E402
                                   SearchContext, TraceTable)
from repro.obs import (BYTE_BUCKETS, CANONICAL_STATS, DecisionLog,  # noqa: E402
                       Histogram, MetricRegistry, NULL_TRACER, SpanTracer,
                       TRACK_SCOPE)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------

def test_counter_only_goes_up():
    reg = MetricRegistry()
    c = reg.counter("fleet_requests_served_total", "served")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_get_or_create_returns_the_live_child():
    reg = MetricRegistry()
    a = reg.counter("serve_decode_tokens_total", "tokens", engine="r0")
    b = reg.counter("serve_decode_tokens_total", "tokens", engine="r0")
    assert a is b                        # instrumented code holds the child
    other = reg.counter("serve_decode_tokens_total", "tokens", engine="r1")
    assert other is not a                # distinct label set, distinct series


def test_registry_rejects_kind_mismatch_and_bad_names():
    reg = MetricRegistry()
    reg.counter("fleet_ttft_seconds")
    with pytest.raises(ValueError):
        reg.histogram("fleet_ttft_seconds")       # already a counter
    with pytest.raises(ValueError):
        reg.counter("bad-metric-name")
    with pytest.raises(ValueError):
        reg.gauge("ok_name", **{"bad-label": 1})


def test_histogram_percentile_brackets_the_exact_value():
    """The histogram answers percentiles at bucket resolution: its answer
    is a bucket upper bound that covers (and stays within one bucket step
    of) the exact percentile computed from the raw samples by the shared
    ``benchmarks.common.percentile`` helper."""
    samples = [0.002] * 51 + [0.02] * 30 + [0.2] * 15 + [2.0] * 5
    h = Histogram()
    for v in samples:
        h.observe(v)
    assert h.count == len(samples)
    assert h.sum == pytest.approx(sum(samples))
    for q in (50, 90, 99):
        exact = percentile(samples, q)
        bound = h.percentile(q)
        assert bound in h.buckets
        assert exact <= bound <= 1.3 * exact, (q, exact, bound)
    assert Histogram().percentile(50) == 0.0     # empty histogram


def _filled_registry() -> MetricRegistry:
    """Deterministic fill exercising every family kind, multiple series
    per family, both bucket lists, and overflow (+Inf) samples."""
    reg = MetricRegistry()
    c = reg.counter("fleet_requests_served_total",
                    "Requests finished fleet-wide", fleet="fleet")
    c.inc()
    c.inc(2)
    reg.counter("fleet_requests_served_total",
                "Requests finished fleet-wide", fleet="west").inc(5)
    reg.gauge("serve_utilization", "Batch-slot occupancy",
              engine="fleet/r0").set(0.25)
    h = reg.histogram("fleet_ttft_seconds", "Client-facing TTFT",
                      fleet="fleet")
    for v in (0.0004, 0.003, 0.003, 0.08, 0.7, 42.0):   # 42 -> +Inf slot
        h.observe(v)
    reg.histogram("region_ship_bytes", "Session wire payload",
                  buckets=BYTE_BUCKETS, region="region").observe(2048.0)
    return reg


def test_prometheus_text_matches_golden():
    text = _filled_registry().prometheus_text()
    with open(os.path.join(GOLDEN, "metrics.prom")) as f:
        assert text == f.read()


def test_prometheus_histogram_buckets_are_cumulative():
    text = _filled_registry().prometheus_text()
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("fleet_ttft_seconds_bucket")]
    assert counts == sorted(counts)              # le-buckets never decrease
    assert counts[-1] == 6                       # +Inf covers every sample


def test_snapshot_is_json_able_and_consistent():
    snap = _filled_registry().snapshot()
    snap2 = json.loads(json.dumps(snap))         # round-trips losslessly
    assert snap2 == snap
    ttft = snap["fleet_ttft_seconds"]["series"][0]
    assert sum(ttft["bucket_counts"]) == ttft["count"] == 6
    assert len(ttft["bucket_counts"]) == len(ttft["buckets"]) + 1


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def _scripted_tracer() -> SpanTracer:
    """A deterministic-clock tracer replaying a migrated request's life:
    admit -> prefill -> decode on r0 -> migrate -> decode on r1 -> finish,
    plus a WAN ship span on the region track."""
    state = {"now": 0.0}

    def clock() -> float:
        state["now"] = round(state["now"] + 0.001, 6)
        return state["now"]

    tr = SpanTracer(name="fleet", clock=clock)
    tid = tr.trace_for(7)
    assert tid == "fleet/r7"
    tr.instant("admit", tid, "fleet", replica=0)
    tr.complete("prefill", tid, "fleet/r0", ts=0.002, dur=0.004,
                prompt_len=8)
    tr.complete("decode-chunk", tid, "fleet/r0", ts=0.007, dur=0.006,
                tokens=4)
    tr.instant("migrate-out", tid, "fleet/r0")
    with tr.span("wan-ship", tid, "region", src=0, dst=1):
        pass
    tr.adopt(7, tid)                   # the importing side re-binds rid 7
    tr.instant("migrate-in", tid, "fleet/r1")
    tr.complete("decode-chunk", tid, "fleet/r1", ts=0.020, dur=0.005,
                tokens=4)
    tr.instant("finish", tid, "fleet/r1")
    return tr


def test_chrome_trace_matches_golden():
    rendered = json.dumps(_scripted_tracer().chrome_trace(), indent=1,
                          sort_keys=True)
    with open(os.path.join(GOLDEN, "trace.json")) as f:
        assert rendered == f.read()


def test_chrome_trace_schema():
    """Structural contract of the export: valid JSON, only X/i/M phases,
    non-negative monotone timestamps, durations on spans, and every
    pid/tid named by a metadata event."""
    ct = json.loads(json.dumps(_scripted_tracer().chrome_trace()))
    events = ct["traceEvents"]
    assert events and ct["displayTimeUnit"] == "ms"
    assert {e["ph"] for e in events} <= {"X", "i", "M"}
    data = [e for e in events if e["ph"] != "M"]
    ts = [e["ts"] for e in data]
    assert ts == sorted(ts) and ts[0] == 0.0     # relative to first event
    for e in data:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        else:
            assert e["s"] == "t"
    named_pids = {e["pid"] for e in events
                  if e["ph"] == "M" and e["name"] == "process_name"}
    named_tids = {e["tid"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {e["pid"] for e in data} <= named_pids
    assert {e["tid"] for e in data} <= named_tids


def test_tracer_timeline_and_tracks_follow_one_trace():
    tr = _scripted_tracer()
    tl = tr.timeline("fleet/r7")
    assert [e["ts"] for e in tl] == sorted(e["ts"] for e in tl)
    assert tr.tracks("fleet/r7") == ["fleet", "fleet/r0", "region",
                                     "fleet/r1"]
    assert tr.timeline("no-such-trace") == []


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.trace_for(3) is None
    NULL_TRACER.instant("x")
    NULL_TRACER.complete("x", ts=0.0, dur=1.0)
    with NULL_TRACER.span("x"):
        pass                                     # no state, no events


def test_tracer_event_cap_evicts_oldest():
    tr = SpanTracer(name="t", cap=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert [e["name"] for e in tr.events] == ["e6", "e7", "e8", "e9"]


def _profiled(tmp_path, body) -> dict:
    """Run ``body`` inside a profiler session; the host events of the
    ``.xplane.pb`` it wrote, by name."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    return {e.name: e for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_context_spans_reach_the_profiler_trace_with_their_args(tmp_path):
    tr = SpanTracer("t")

    def body():
        with tr.span("outer", TRACK_SCOPE, "e0", active=3) as args:
            with tr.span("inner", tr.trace_for(5), "e0"):
                pass
            args["admitted"] = 2
        tr.complete("after-the-fact", TRACK_SCOPE, "e0", ts=0.0, dur=1.0)
        tr.instant("point", TRACK_SCOPE, "e0")
        with NULL_TRACER.span("null-span") as null_args:
            assert null_args == {}

    events = _profiled(tmp_path, body)
    outer, inner = events["outer"], events["inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.end_ns <= outer.end_ns
    assert dict(outer.stats) == {"track": "e0", "active": 3, "admitted": 2}
    assert dict(inner.stats) == {"track": "e0"}
    # only context spans have a second sink; the null tracer has none
    assert not {"after-the-fact", "point", "null-span"} & set(events)
    by_name = {e["name"]: e for e in tr.events}
    assert list(by_name) == ["inner", "outer", "after-the-fact", "point"]
    assert by_name["outer"]["args"] == {"active": 3, "admitted": 2}
    assert by_name["outer"]["trace"] == "t"
    assert by_name["inner"]["trace"] == "t/r5"


def test_track_scoped_events_survive_sampling():
    tr = SpanTracer("t", sample_rate=4)
    assert tr.trace_for(1) is None
    with tr.span("request", tr.trace_for(1), "e0"):
        pass
    with tr.span("step", TRACK_SCOPE, "e0"):
        pass
    tr.complete("chunk", TRACK_SCOPE, "e0", ts=0.0, dur=1.0)
    tr.instant("tick", TRACK_SCOPE)
    with tr.track_span("phase", "e0", slot=1) as args:
        args["admitted"] = 2
    assert [(e["name"], e["trace"], e["track"]) for e in tr.events] == [
        ("step", "t", "e0"), ("chunk", "t", "e0"), ("tick", "t", "t"),
        ("phase", "t", "e0")]
    assert tr.events[-1]["args"] == {"slot": 1, "admitted": 2}
    with NULL_TRACER.track_span("phase", "e0", slot=1) as args:
        assert args == {"slot": 1}


# ---------------------------------------------------------------------------
# decision attribution
# ---------------------------------------------------------------------------

def test_search_attribution_terms_sum_to_total():
    """The additivity invariant at its source: a composed Sum cost scored
    through ``TraceTable.search`` yields per-term breakdowns summing to
    each candidate's total, with repeated model classes disambiguated."""
    t = TraceTable([3])
    for r, v in enumerate((2.0, 0.5, 1.0)):
        t.update((r,), v)
    got = []
    ctx = SearchContext(attribution=got.append)
    cost = Latency() + Occupancy() + Latency()   # Latency twice on purpose
    chosen = t.search([Candidate(key=(r,), item=r, width=2)
                       for r in range(3)], cost, ctx=ctx)
    assert chosen == 1                           # min 3*value with width 2
    (sa,) = got
    assert sa.chosen == 1 and sa.policy == "GlobalSearch"
    assert len(sa.candidates) == 3
    for c in sa.candidates:
        assert set(c.terms) == {"Latency", "Occupancy", "Latency#2"}
        assert sum(c.terms.values()) == pytest.approx(c.total, abs=1e-12)
        assert c.terms["Occupancy"] == pytest.approx(2 * c.value)


def test_decision_log_hook_records_and_annotates():
    t = TraceTable([2])
    t.update((0,), 1.0)
    t.update((1,), 3.0)
    log = DecisionLog()
    hook = log.hook("route", lambda sa: {c.item: {"v": c.value}
                                         for c in sa.candidates},
                    req_class="DECODE")
    recbox = []
    ctx = SearchContext(attribution=lambda sa: recbox.append(hook(sa)))
    t.search([Candidate(key=(r,), item=r) for r in range(2)],
             Latency(), ctx=ctx)
    rec = recbox[0]
    rec.meta.update(replica=rec.chosen, action="ADMIT")  # post-hoc annotate
    assert log.last("route") is rec and log.last("nope") is None
    assert rec.check()
    assert rec.chosen == 0 and rec.rows[1] == {"v": 3.0}
    assert rec.breakdown() == {"Latency": 1.0}
    with pytest.raises(KeyError):
        rec.candidate(99)
    text = DecisionLog.explain(rec)
    assert "chose 0" in text and "Latency=" in text and "ADMIT" in text


def test_fleet_benchmark_every_decision_carries_a_valid_breakdown():
    """ISSUE acceptance: run the fleet routing benchmark with a
    DecisionLog attached — every routing decision must land there with a
    per-term cost breakdown summing to each candidate's total, and the
    final post-admission outcome annotated."""
    from benchmarks.fleet_routing import N_REPLICAS, simulate

    log = DecisionLog()
    res = simulate("ptt", n_requests=300, seed=0, attribution=log)
    assert res["n"] > 0 and len(log) > 100       # one record per search
    assert {r.kind for r in log.records} == {"route"}
    for rec in log.records:
        assert rec.check(), DecisionLog.explain(rec)
        assert rec.meta["action"] in ("ADMIT", "QUEUE", "SHED")
        assert rec.meta["replica"] in range(N_REPLICAS)
        assert set(rec.rows) == {c.item for c in rec.search.candidates}
    admitted = [r for r in log.records if r.meta["action"] == "ADMIT"]
    # the annotated final pick is a real candidate of the search (overflow
    # may legally override the search's own chosen item)
    for rec in admitted[:50]:
        assert rec.candidate(rec.meta["replica"]).terms


# ---------------------------------------------------------------------------
# unified stats() facades
# ---------------------------------------------------------------------------

class _NullModel:
    """ServeEngine.__init__ only reads the jitted decode handle; a stats
    facade test never steps the engine, so a None handle suffices."""
    decode_fused = None


def test_stats_facades_share_canonical_keys_with_legacy_aliases():
    from repro.region import RegionGateway
    from repro.router import FleetGateway
    from repro.serve import ServeEngine

    engines = [ServeEngine(_NullModel(), None, max_batch=2, max_seq=8)
               for _ in range(2)]
    gw = FleetGateway(engines)
    region = RegionGateway([gw])
    scales = {"engine": engines[0].stats(), "fleet": gw.stats(),
              "region": region.stats()}
    for name, s in scales.items():
        for key in CANONICAL_STATS:
            assert key in s, (name, key)
            assert isinstance(s[key], (int, float)), (name, key)
    # legacy aliases stay and agree with the canonical counters
    e, f, r = scales["engine"], scales["fleet"], scales["region"]
    assert e["sessions_migrated"] == (e["sessions_exported"]
                                      + e["sessions_imported"])
    assert f["served"] == f["requests_served"]
    assert f["migrations"] == f["sessions_migrated"]
    assert r["wan_ships"] == r["sessions_migrated"]
    assert r["requests_served"] == f["requests_served"]


# ---------------------------------------------------------------------------
# end-to-end: a migrated request keeps ONE timeline (real engines)
# ---------------------------------------------------------------------------

def test_migrated_request_keeps_one_causal_timeline():
    """ISSUE acceptance: quarantine-drain a live decode session between
    two real engines under one shared tracer; the request's exported trace
    must be a single trace id whose timeline runs contiguously from the
    source replica through migrate-out/migrate-in to the destination."""
    import jax

    from repro.configs import get_config
    from repro.models import get_model
    from repro.router import FleetGateway
    from repro.serve import Request, ServeEngine

    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    engines = [ServeEngine(m, params, max_batch=2, max_seq=48)
               for _ in range(2)]
    gw = FleetGateway(engines)
    tracer, registry = SpanTracer(name="fleet"), MetricRegistry()
    gw.attach_obs(tracer, registry, name="fleet")
    assert engines[0].tracer is tracer           # propagated downward
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6), max_new=12)
            for i in range(4)]
    for r in reqs:
        gw.submit(r)
    for _ in range(3):
        gw.pump()
    victim = max(range(2), key=lambda i: engines[i].active_count())
    gw.router.detector.force_quarantine(victim)
    gw.pump()
    gw.run_until_drained(max_steps=1000)
    assert all(r.done for r in reqs)
    assert gw.stats()["sessions_migrated"] >= 1

    moved = [r.rid for r in reqs
             if any(e["name"] == "migrate-out"
                    for e in tracer.timeline(tracer.trace_for(r.rid)))]
    assert moved, "no traced request migrated"
    tid = tracer.trace_for(moved[0])
    src, dst = f"fleet/r{victim}", f"fleet/r{1 - victim}"
    tracks = tracer.tracks(tid)
    assert src in tracks and dst in tracks       # both replicas, one trace
    tl = tracer.timeline(tid)
    names = [e["name"] for e in tl]
    out_i, in_i = names.index("migrate-out"), names.index("migrate-in")
    assert out_i < in_i < names.index("finish")
    # contiguity: decode work on the source strictly precedes the handoff,
    # decode work on the destination strictly follows it — one causal line
    assert any(e["name"] == "decode-chunk" and e["track"] == src
               for e in tl[:out_i])
    assert any(e["name"] == "decode-chunk" and e["track"] == dst
               for e in tl[in_i:])
    assert not any(e["track"] == src for e in tl[in_i:])
    assert "prefill" in names                    # admission span survived

    # the exported view keeps the request as ONE process (pid)
    ct = tracer.chrome_trace()
    pid = {e["args"]["name"]: e["pid"] for e in ct["traceEvents"]
           if e.get("ph") == "M" and e["name"] == "process_name"}[tid]
    own = [e for e in ct["traceEvents"]
           if e.get("pid") == pid and e["ph"] != "M"]
    assert {"migrate-out", "migrate-in"} <= {e["name"] for e in own}

    # the attached registry saw the migration on both engine facades
    snap = registry.snapshot()
    exports = {s["labels"]["engine"]: s["value"]
               for s in snap["serve_sessions_exported_total"]["series"]}
    assert exports[src] >= 1
    assert snap["fleet_sessions_migrated_total"]["series"][0]["value"] >= 1
    assert snap["serve_decode_step_seconds"]["series"][0]["count"] > 0


# ---------------------------------------------------------------------------
# golden regeneration
# ---------------------------------------------------------------------------

if __name__ == "__main__" and "--regen" in sys.argv:
    os.makedirs(GOLDEN, exist_ok=True)
    with open(os.path.join(GOLDEN, "metrics.prom"), "w") as f:
        f.write(_filled_registry().prometheus_text())
    with open(os.path.join(GOLDEN, "trace.json"), "w") as f:
        f.write(json.dumps(_scripted_tracer().chrome_trace(), indent=1,
                           sort_keys=True))
    print(f"regenerated golden fixtures under {GOLDEN}")
