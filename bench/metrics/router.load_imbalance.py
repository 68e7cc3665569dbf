"""Fleet routing (``router/``): max / mean over replicas of the decode
tokens each replica kept inside the measured window, from the harness's
step log.  1.0 is a perfectly even fleet.  Moves ``ttft_p95_ms``."""


def read(run):
    if run.replicas < 2 or not run.steps:
        return None
    w = run.window
    tokens = [0] * run.replicas
    for s in run.steps:
        if s.t0 >= w.w_open and s.t1 <= w.w_close:
            tokens[s.replica] += sum(len(g) for g in s.decode_rows)
    mean = sum(tokens) / run.replicas
    return max(tokens) / mean if mean > 0 else None
