"""The trace reduction, on a quarter second of the long-chat cell recorded
on one TPU v5e: one ``decode_fused`` call and one prefill chunk."""

import pathlib

import pytest

from bench import trace_reduce as T

FIXTURE = (pathlib.Path(__file__).resolve().parents[1] / "fixtures"
           / "qwen2-0.5b.long-chat.xplane.pb.gz")
CUSTOM = r'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def trace():
    return T.load(str(FIXTURE))


def test_device_and_harness_spans(trace):
    assert [d.index for d in trace.devices] == [0]
    names = {h[0] for h in trace.host}
    assert {"bench.trace", "bench.step"} <= names
    t0, t1 = trace.span("bench.trace")
    assert 0 < t1 - t0 < 1e9


def test_busy_is_a_union_inside_the_window(trace):
    d = trace.devices[0]
    t0, t1 = trace.span("bench.trace")
    busy = d.busy(t0, t1)
    assert all(a < b <= c for (a, b), (c, _) in zip(busy, busy[1:]))
    total = T.covered(busy)
    assert 0.5 * (t1 - t0) < total <= t1 - t0


def test_program_and_kernel_time(trace):
    d = trace.devices[0]
    t0, t1 = trace.span("bench.trace")
    fused = T.module_time(d, r"^jit_fused\(", t0, t1)
    chunk = T.module_time(d, r"^jit_chunk\(", t0, t1)
    assert fused > 0 and chunk > 0
    dec = T.kernel_ops(d, r"^jit_fused\(", CUSTOM, t0, t1)
    pre = T.kernel_ops(d, r"^jit_chunk\(", CUSTOM, t0, t1)
    # one call per layer per decoded position: 24 layers x 4, and 24
    assert len(dec) == 96 and len(pre) == 24
    assert 0 < sum(o.dur for o in dec) < fused
    assert 0 < sum(o.dur for o in pre) < chunk
    assert all(o.module.startswith("jit_fused(") for o in dec)


def test_merge_and_gaps():
    assert T.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    dev = T.Device(0, [T.Op("a", 0, 10), T.Op("b", 30, 10)], [])
    gaps = T.idle_gaps(dev, [("bench.sleep", 12, 29)], 0, 50)
    assert gaps[0] == ["bench.sleep", 20e-9]
    assert gaps[1] == ["no harness span", 10e-9]


def test_breakdown_lists(trace):
    d = trace.devices[0]
    t0, t1 = trace.span("bench.trace")
    top = T.top_ops([d], t0, t1)
    assert 0 < len(top) <= 10
    assert all(k.split("/")[1] not in T.CONTAINERS for k, _ in top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    gaps = T.idle_gaps(d, trace.host, t0, t1)
    assert 0 < len(gaps) <= 10
    assert all(label.startswith("bench.") or label == "no harness span"
               for label, _ in gaps)
