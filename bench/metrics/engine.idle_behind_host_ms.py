"""Engine step (``serve/engine.py``): the mean over the ``engine.step``
spans inside the traced window of the time the step's chip runs no
operation while the step is outside its ``engine.*.sync`` phases: the
device idle that host work in the engine causes.  The chip's operations
are first shifted onto the host's clock, step by step
(``program_spans.clock_offset``); a step whose shift cannot be measured,
or does not hold, is left out.  At most ``engine.host_ms``.  Moves
``tpot_p95_ms``."""

from bench import program_spans


def read(run):
    steps = program_spans.steps(run)
    devs = {d.index: d for d in run.devices()}
    if not steps or not devs:
        return None
    execs = {i: program_spans.executions(i) for i in devs}
    idle = []
    for s in steps:
        if s.chip not in devs:
            continue
        off = program_spans.clock_offset(s, execs[s.chip])
        if off is None:
            continue
        t0, t1 = s.span.start, s.span.end
        b = [(a + off, e + off) for a, e in devs[s.chip].busy(t0 - off,
                                                              t1 - off)]
        idle.append(program_spans.idle_ns(b, t0, t1)
                    - sum(program_spans.idle_ns(b, c.start, c.end)
                          for c in s.syncs))
    if not idle:
        return None
    return sum(idle) / len(idle) / 1e6
