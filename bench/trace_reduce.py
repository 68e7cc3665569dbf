"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device-busy intervals, device time per program and per kernel, the
device operations that took most time, and idle gaps labelled with the
harness span open during them.

The trace is read with ``jax.profiler.ProfileData`` alone.  Times are
nanoseconds on the trace's own clock, which the device planes and the host
threads share; the harness's ``TraceAnnotation`` spans sit on the host
threads, so a host span and a device op can be compared directly.
"""

from __future__ import annotations

import dataclasses
import gzip
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str                    # for an op, its HLO text
    start: float
    dur: float
    module: str = ""             # the program execution it ran inside


@dataclasses.dataclass
class Device:
    index: int
    ops: list[Op]
    modules: list[Op]            # whole-program executions

    def busy(self, t0: float, t1: float) -> list[tuple[float, float]]:
        return merge([(max(o.start, t0), min(o.start + o.dur, t1))
                      for o in self.ops
                      if o.start < t1 and o.start + o.dur > t0])


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[tuple[str, float, float]]    # harness spans: name, start, end

    def span(self, name: str) -> tuple[float, float] | None:
        for n, s, e in self.host:
            if n == name:
                return s, e
        return None


def merge(intervals) -> list[tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def _events(line) -> list[Op]:
    return [Op(name=e.name, start=float(e.start_ns),
               dur=float(e.duration_ns)) for e in line.events]


def _attribute(ops: list[Op], modules: list[Op]) -> None:
    """Name each op's program: the module execution whose interval holds
    the op's start (executions on one chip do not overlap)."""
    mods = sorted(modules, key=lambda m: m.start)
    i = 0
    for o in sorted(ops, key=lambda o: o.start):
        while i < len(mods) and mods[i].start + mods[i].dur < o.start:
            i += 1
        if i < len(mods) and mods[i].start <= o.start:
            o.module = mods[i].name


def load(path: str) -> Trace:
    """Read ``path``: an ``.xplane.pb``, or one compressed with gzip."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            _attribute(ops, modules)
            devices.append(Device(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.end_ns)))
    devices.sort(key=lambda d: d.index)
    host.sort(key=lambda h: h[1])
    return Trace(devices, host)


def module_time(dev: Device, pattern: str, t0: float, t1: float) -> float:
    """Device time of the program executions whose name matches
    ``pattern`` (``jit_<function>(<id>)``) that started in [t0, t1)."""
    rx = re.compile(pattern)
    return sum(m.dur for m in dev.modules
               if rx.search(m.name) and t0 <= m.start < t1)


def kernel_ops(dev: Device, module: str, op: str, t0: float,
               t1: float) -> list[Op]:
    """The ops matching ``op`` that ran inside programs matching
    ``module`` and started in [t0, t1)."""
    rm, ro = re.compile(module), re.compile(op)
    return [o for o in dev.ops if t0 <= o.start < t1
            and rm.search(o.module) and ro.search(o.name)]


def op_kind(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ")[0].lstrip("%")
    return head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() \
        else head


CONTAINERS = ("while", "conditional", "call")


def top_ops(devices: list[Device], t0: float, t1: float,
            n: int = 10) -> list[list]:
    """Device time per kind of op, within the program it ran in, summed
    over ``devices``, most first (seconds).  Loops and calls are left out:
    their time is their body's ops."""
    total: dict[str, float] = {}
    for d in devices:
        for o in d.ops:
            kind = op_kind(o.name)
            if t0 <= o.start < t1 and kind not in CONTAINERS:
                key = f"{o.module.split('(')[0]}/{kind}"
                total[key] = total.get(key, 0.0) + o.dur
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(dev: Device, host, t0: float, t1: float,
              n: int = 10) -> list[list]:
    """The longest gaps with no op on ``dev`` inside [t0, t1], each
    labelled with the innermost harness span open at its midpoint
    (seconds), most first."""
    busy = dev.busy(t0, t1)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_spans = [h for h in host if h[1] <= mid < h[2]]
        label = (max(open_spans, key=lambda h: h[1])[0]
                 if open_spans else "no harness span")
        labelled.append([label, (e - s) / 1e9])
    return labelled
