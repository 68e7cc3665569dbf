"""Device (TPU v5e): 1 - (union of device op intervals / traced window),
the mean over the chips the cell uses, in %.  Moves ``tpot_p95_ms``."""

from bench import trace_reduce


def read(run):
    devs = run.devices()
    if not devs:
        return None
    t0, t1 = run.traced_ns
    idle = [1.0 - trace_reduce.covered(d.busy(t0, t1)) / (t1 - t0)
            for d in devs]
    return 100.0 * sum(idle) / len(idle)
