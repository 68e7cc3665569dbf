"""Engine step (``serve/engine.py``): the window's total wall time inside
``ServeEngine.step`` calls divided by the number of calls, on the harness
clock (a step ends in its token sync).  Moves ``tpot_p95_ms``."""


def read(run):
    w = run.window
    steps = [s for s in run.steps if s.t0 >= w.w_open and s.t1 <= w.w_close]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
