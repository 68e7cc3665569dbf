"""What a traced run records around the program, from the harness.

* :class:`StepLog` wraps each engine's ``step`` (an attribute of the engine
  object; the program is not edited).  It records the host time of every
  call and, for each decode token the call kept, the KV rows that token
  attended, grouped by its place in the decode chunk: one group is one
  ``ragged_decode`` call per layer.
* A ``SpanTracer`` of the program's own, attached to every engine while
  the profiler runs, gives the ``prefill-chunk`` spans: which prompt chunk
  ran when, with how many live tokens.
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Step:
    replica: int
    t0: float
    t1: float
    decode_rows: list[list[int]]     # per chunk position: rows per token


class StepLog:
    def __init__(self, engines: list):
        self.steps: list[Step] = []
        self._seen: dict[int, int] = {}
        for i, e in enumerate(engines):
            e.step = self._wrap(i, e, e.step)

    def _wrap(self, replica: int, engine, step):
        def timed():
            before = [(r, len(r.out_tokens)) for r in engine.active
                      if r is not None]
            t0 = time.perf_counter()
            out = step()
            t1 = time.perf_counter()
            ids = {id(r) for r, _ in before}
            # requests slotted during this step decode in it too; they
            # hold the tokens counted when they were last seen (1: the
            # token their prefill produced)
            fresh = [(r, self._seen.get(r.rid, 1)) for r in engine.active
                     if r is not None and id(r) not in ids]
            groups: list[list[int]] = []
            for r, c0 in before + fresh:
                c1 = len(r.out_tokens)
                plen = len(r.prompt)
                for s, j in enumerate(range(c0, c1)):
                    if s == len(groups):
                        groups.append([])
                    # decode token j was produced at position plen + j - 1,
                    # attending rows 0 .. plen + j - 1
                    groups[s].append(plen + j)
                if r.done:
                    self._seen.pop(r.rid, None)
                else:
                    self._seen[r.rid] = c1
            self.steps.append(Step(replica, t0, t1, groups))
            return out
        return timed

    def between(self, t0: float, t1: float) -> list[Step]:
        return [s for s in self.steps if s.t0 >= t0 and s.t1 <= t1]


def prefill_chunks(tracer, t0: float, t1: float) -> list[dict]:
    """The program's ``prefill-chunk`` spans that ran within [t0, t1]:
    ``rid``, ``start``, ``qlen``, ``ts``, ``dur`` (host seconds)."""
    out = []
    for ev in tracer.events:
        if ev["name"] != "prefill-chunk" or ev.get("ph") != "X":
            continue
        if ev["ts"] < t0 or ev["ts"] + ev["dur"] > t1:
            continue
        rid = int(str(ev["trace"]).rsplit("/r", 1)[1])
        qlen = int(ev["args"]["tokens"])
        out.append({"rid": rid, "qlen": qlen,
                    "start": int(ev["args"]["consumed"]) - qlen,
                    "ts": ev["ts"], "dur": ev["dur"]})
    return out
