"""Engine step (``serve/engine.py``): the mean over the ``engine.step``
spans inside the traced window of the span's duration minus its
``engine.prefill.sync`` and ``engine.decode.sync`` phases: the host time a
step spends not waiting on the device, from the program's own spans on
the trace's clock.  Moves ``tpot_p95_ms``."""

from bench import program_spans


def read(run):
    steps = program_spans.steps(run)
    if not steps:
        return None
    return sum(s.host_ns() for s in steps) / len(steps) / 1e6
