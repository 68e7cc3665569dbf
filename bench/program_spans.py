"""The program's own spans in a traced run, and the clock that puts the
chips' operations against them.

While the profiler runs, every context span of ``repro.obs.SpanTracer`` is
also a ``TraceAnnotation`` in the profiler's trace, with the span's track
and args as the event's stats.  An engine writes one ``engine.step`` span
per ``ServeEngine.step`` call (args ``active``, ``capacity``, ``queued``,
``prefilling``, ``backlog_tokens`` and the id of its ``device``, taken at
its start) and, on the same track, one span per phase inside it, among
them ``engine.prefill.sync`` and ``engine.decode.sync``: the waits for the
device.

A chip's times in the trace do not share the host's clock exactly: on a
TPU v5e they read 0.3–1.5 ms early, by an amount that differs from trace
to trace and can step within one.  :func:`clock_offset` measures the
shift around each step from the runtime's own host event
``DoEnqueueProgram``, which names the chip and the program execution
(``run_id``) it hands over: no execution starts before the host began to
enqueue it, so the shift is at least the largest lead of an execution's
start over its enqueue.  That bound is tight when an execution found its
chip idle, as the first program of every engine step does.

This module reads the spans and the enqueues out of the ``.xplane.pb``
that ``bench/run.py`` wrote for the run (the one file under its trace
directory), parsing each file once, and keeps the steps that lie wholly
inside the run's traced window.  A program that writes no such spans
reads as no steps.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import os
import pathlib
import re

PREFIX = "engine."
SYNCS = ("engine.prefill.sync", "engine.decode.sync")
ENQUEUE = "DoEnqueueProgram"       # host: the runtime hands a chip a program
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Span:
    name: str
    track: str
    start: float                 # ns on the trace's clock
    end: float
    args: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Step:
    span: Span                   # the engine.step span
    phases: list[Span]           # the spans inside it on its track

    @property
    def chip(self) -> int | None:
        """The id of the device the engine ran on (``None`` for an engine
        spread over several)."""
        return self.span.args.get("device")

    @property
    def syncs(self) -> list[Span]:
        return [p for p in self.phases if p.name in SYNCS]

    def host_ns(self) -> float:
        """The step's time outside its waits for the device."""
        return self.span.dur - sum(s.dur for s in self.syncs)


@dataclasses.dataclass(frozen=True)
class Execution:
    """One program execution, matched by chip and ``run_id``: when the
    host began to enqueue it (host clock), and its run on the chip (the
    chip's clock)."""
    chip: int
    enqueued: float
    start: float
    end: float


@dataclasses.dataclass(frozen=True)
class Profile:
    spans: tuple[Span, ...]
    executions: tuple[Execution, ...]


def trace_file() -> pathlib.Path | None:
    """The profile that ``bench/run.py`` wrote for this run, if any."""
    from bench.run import TRACE_DIR
    return next(TRACE_DIR.rglob("*.xplane.pb"), None)


@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int) -> Profile:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    spans, enqueued, ran = [], {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            ran[(int(m.group(1)), run_id)] = (
                                float(e.start_ns), float(e.end_ns))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ENQUEUE:
                    st = dict(e.stats)
                    if "run_id" in st and "device_ordinal" in st:
                        enqueued[(st["device_ordinal"], st["run_id"])] = \
                            float(e.start_ns)
                elif e.name.startswith(PREFIX):
                    args = dict(e.stats)
                    spans.append(Span(e.name, str(args.pop("track", "")),
                                      float(e.start_ns), float(e.end_ns),
                                      args))
    spans.sort(key=lambda s: s.start)
    execs = sorted((Execution(k[0], enqueued[k], *ran[k])
                    for k in enqueued.keys() & ran.keys()),
                   key=lambda x: x.start)
    return Profile(tuple(spans), tuple(execs))


def profile(path) -> Profile:
    """The ``engine.*`` spans and the matched executions in ``path``
    (``.xplane.pb``, or gzipped)."""
    path = str(path)
    return _parse(path, os.stat(path).st_mtime_ns)


def load(path) -> tuple[Span, ...]:
    """Every ``engine.*`` span in ``path``."""
    return profile(path).spans


def steps(run) -> list[Step]:
    """The ``engine.step`` spans of ``run`` that lie inside its traced
    window, each with its phase spans."""
    path = trace_file()
    if path is None or run.traced_ns is None:
        return []
    t0, t1 = run.traced_ns
    inside = [s for s in load(path) if t0 <= s.start and s.end <= t1]
    out = []
    for s in inside:
        if s.name != "engine.step":
            continue
        out.append(Step(s, [p for p in inside if p is not s
                            and p.track == s.track
                            and s.start <= p.start and p.end <= s.end]))
    return out


def executions(chip: int) -> list[Execution]:
    """The matched program executions on ``chip`` in the run's trace."""
    path = trace_file()
    if path is None:
        return []
    return [x for x in profile(path).executions if x.chip == chip]


def clock_offset(step: Step, execs: list[Execution]) -> float | None:
    """Nanoseconds to add to the chip's times to put them on the host's
    clock around ``step``: the largest lead of a program's start over the
    start of its enqueue, over the programs the step enqueued.  The first
    of them finds the chip idle, since the step before waited for its
    last program, so the lead is tight.  ``None`` where the step enqueued
    no matched program, or where the shifted trace still breaks causality
    on the other side: the decode program, enqueued inside the step's
    ``engine.decode.dispatch``, ending after ``engine.decode.sync``
    returned its tokens."""
    t0, t1 = step.span.start, step.span.end
    mine = [x for x in execs if t0 <= x.enqueued <= t1]
    if not mine:
        return None
    offset = max(x.enqueued - x.start for x in mine)
    dispatch = [p for p in step.phases if p.name == "engine.decode.dispatch"]
    sync = [p for p in step.phases if p.name == "engine.decode.sync"]
    if dispatch and sync and any(
            x.end + offset > sync[-1].end for x in mine
            if dispatch[-1].start <= x.enqueued <= dispatch[-1].end):
        return None
    return offset


def idle_ns(busy: list[tuple[float, float]], t0: float, t1: float) -> float:
    """Time in [t0, t1] that sorted, disjoint ``busy`` intervals leave
    uncovered."""
    i = max(bisect.bisect_right(busy, (t0,)) - 1, 0)
    covered = 0.0
    for s, e in busy[i:]:
        if s >= t1:
            break
        covered += max(min(e, t1) - max(s, t0), 0.0)
    return (t1 - t0) - covered
