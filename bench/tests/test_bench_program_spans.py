"""The program's own spans, read out of the profiler's trace: on half a
second of the long-chat cell recorded on one TPU v5e with the engine's
phase spans, on the older recording of the cell that has none, and end
to end in a traced run of the tiny cell on the CPU."""

import pathlib

import pytest

from bench import loader, program_spans, trace_reduce
from bench.record import RunRecord
from bench.tests import tiny

FIXTURES = pathlib.Path(__file__).resolve().parents[1] / "fixtures"
SPANS = FIXTURES / "qwen2-0.5b.long-chat.spans.xplane.pb.gz"
NO_SPANS = FIXTURES / "qwen2-0.5b.long-chat.xplane.pb.gz"
METRICS = ("engine.host_ms", "engine.idle_behind_host_ms",
           "engine.batch_occupancy", "engine.prefill_backlog_tokens")


def _run(path) -> RunRecord:
    tr = trace_reduce.load(str(path))
    return RunRecord(dims=None, peaks=None, replicas=1, records=[],
                     window=None, trace=tr, traced_ns=tr.span("bench.trace"))


def _read(monkeypatch, path) -> dict:
    monkeypatch.setattr(program_spans, "trace_file", lambda: path)
    run = _run(path)
    return {m: loader.load_reader(m).read(run) for m in METRICS}


def test_chip_fixture_steps_and_phases(monkeypatch):
    monkeypatch.setattr(program_spans, "trace_file", lambda: SPANS)
    steps = program_spans.steps(_run(SPANS))
    assert steps
    for s in steps:
        names = {p.name for p in s.phases}
        assert {"engine.admit", "engine.decode.dispatch",
                "engine.decode.sync", "engine.harvest"} <= names
        assert s.span.args["capacity"] == 64 and s.chip == 0
        assert 0 < s.host_ns() < s.span.dur
        assert all(s.span.start <= p.start and p.end <= s.span.end
                   for p in s.phases)


def test_readers_on_the_chip_fixture(monkeypatch):
    v = _read(monkeypatch, SPANS)
    steps = program_spans.steps(_run(SPANS))
    longest = max(s.span.dur for s in steps) / 1e6
    assert 0 < v["engine.host_ms"] <= longest
    assert 0 <= v["engine.idle_behind_host_ms"] <= v["engine.host_ms"]
    assert 0 < v["engine.batch_occupancy"] <= 100
    assert v["engine.prefill_backlog_tokens"] >= 0


def test_the_chip_clock_is_shifted_onto_the_host(monkeypatch):
    monkeypatch.setattr(program_spans, "trace_file", lambda: SPANS)
    steps = program_spans.steps(_run(SPANS))
    execs = program_spans.executions(0)
    assert execs and len(execs) == len(program_spans.profile(SPANS)
                                       .executions)
    # unshifted, programs start before the host began to enqueue them: the
    # chip's times read early, by 0.3-1.5 ms on a TPU v5e
    assert any(x.start < x.enqueued for x in execs)
    for s in steps:
        off = program_spans.clock_offset(s, execs)
        assert 0.2e6 < off < 2.0e6
        mine = [x for x in execs
                if s.span.start <= x.enqueued <= s.span.end]
        assert all(x.start + off >= x.enqueued for x in mine)


def test_the_kernels_carry_their_names_on_the_chip():
    tr = trace_reduce.load(str(SPANS))
    d = tr.devices[0]
    t0, t1 = tr.span("bench.trace")
    custom = r'custom_call_target="tpu_custom_call"'
    for program, kernel in ((r"^jit_fused\(", "ragged_decode"),
                            (r"^jit_chunk\(", "ragged_prefill")):
        ops = trace_reduce.kernel_ops(d, program, custom, t0, t1)
        assert ops
        assert {trace_reduce.op_kind(o.name) for o in ops} == {kernel}


def test_readers_find_nothing_in_a_trace_without_program_spans(monkeypatch):
    assert _read(monkeypatch, NO_SPANS) == dict.fromkeys(METRICS)
    monkeypatch.setattr(program_spans, "trace_file", lambda: None)
    assert program_spans.steps(_run(NO_SPANS)) == []


def _step(dispatch, sync):
    span = program_spans.Span
    return program_spans.Step(
        span("engine.step", "e0", 0.0, sync[1] + 1, {"device": 0}),
        [span("engine.decode.dispatch", "e0", *dispatch, {}),
         span("engine.decode.sync", "e0", *sync, {})])


def test_the_clock_offset_is_the_largest_lead_over_an_enqueue():
    X = program_spans.Execution
    # the chip reads 4 early: the program that found it idle started at 10
    # (host 14) when enqueued at 14; the next queued behind it; one
    # enqueued before the step does not count
    execs = [X(0, 14.0, 10.0, 18.0), X(0, 15.0, 20.0, 40.0),
             X(0, -9.0, -20.0, -10.0)]
    step = _step((13.0, 16.0), (16.0, 46.0))
    assert program_spans.clock_offset(step, execs) == 4.0
    assert program_spans.clock_offset(step, execs[2:]) is None
    # shifted by 4, the decode program ends at 44: after a sync that
    # returned its tokens at 43, so the shift does not hold
    late = _step((13.0, 16.0), (16.0, 43.0))
    assert program_spans.clock_offset(late, execs) is None


def test_idle_is_what_the_busy_intervals_leave():
    busy = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    assert program_spans.idle_ns(busy, 0.0, 50.0) == 20.0
    assert program_spans.idle_ns(busy, 5.0, 25.0) == 10.0
    assert program_spans.idle_ns(busy, 12.0, 18.0) == 6.0
    assert program_spans.idle_ns(busy, 45.0, 60.0) == 10.0
    assert program_spans.idle_ns([], 1.0, 4.0) == 3.0


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"))
    return loader.load_cell("tiny.tiny-mix", root=root)


def test_traced_cpu_run_reports_the_program_span_metrics(cell, tmp_path,
                                                          monkeypatch):
    from bench import run
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    res = run.run_cell(cell, 2**31 + 7, 3.0, True, require_chip=False)
    got = res["metrics"]
    # no device plane on the CPU: the idle reader finds nothing to read
    assert "engine.idle_behind_host_ms" not in got
    assert 0 < got["engine.host_ms"]["value"]
    assert 0 < got["engine.batch_occupancy"]["value"] <= 100
    assert got["engine.prefill_backlog_tokens"]["value"] >= 0
    assert got["engine.batch_occupancy"]["unit"] == "%"


def test_tracing_cost_runs_both_arms(cell):
    from bench import tracing_cost
    off = tracing_cost.measure(cell, 2**31 + 9, 2.0, False,
                               require_chip=False)
    on = tracing_cost.measure(cell, 2**31 + 9, 2.0, True,
                              require_chip=False)
    for out in (off, on):
        assert out["correct"] and out["steps"] > 0
        assert out["step_ms_mean"] > 0
    assert "phase_ms_mean" not in off
    assert {"engine.admit", "engine.decode.sync",
            "engine.harvest"} <= set(on["phase_ms_mean"])
