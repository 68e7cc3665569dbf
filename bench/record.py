"""The run record that per-layer metric readers read.

A reader (``bench/metrics/<metric>.py``) gets one :class:`RunRecord` and
returns a number, or ``None`` where it finds nothing to read.  Host times
are ``perf_counter`` seconds; trace times are the trace's nanoseconds.
"""

from __future__ import annotations

import dataclasses

from bench import counting, trace_reduce
from bench.driver import Window
from bench.instrument import Step


@dataclasses.dataclass
class RunRecord:
    dims: counting.Dims
    peaks: counting.Peaks
    replicas: int
    records: list                          # stats.Record per request
    window: Window
    steps: list[Step] = dataclasses.field(default_factory=list)
    prefill_chunks: list[dict] = dataclasses.field(default_factory=list)
    trace: trace_reduce.Trace | None = None
    traced: tuple[float, float] | None = None   # host clock of the trace
    traced_ns: tuple[float, float] | None = None  # the same, trace clock

    def traced_steps(self) -> list[Step]:
        if self.traced is None:
            return []
        t0, t1 = self.traced
        return [s for s in self.steps if s.t0 >= t0 and s.t1 <= t1]

    def traced_chunks(self) -> list[dict]:
        if self.traced is None:
            return []
        t0, t1 = self.traced
        return [c for c in self.prefill_chunks
                if c["ts"] >= t0 and c["ts"] + c["dur"] <= t1]

    def devices(self) -> list[trace_reduce.Device]:
        """The traced devices this run used (replica i on device i)."""
        if self.trace is None:
            return []
        return [d for d in self.trace.devices if d.index < self.replicas]
