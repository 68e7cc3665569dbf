"""Model step (``decode_fused``): model FLOPs of the decode tokens kept
inside the traced window, over the device time of the decode programs
times the chip's bf16 peak, in %.  Moves ``tpot_p95_ms``."""

from bench import counting, trace_reduce

# the jitted functions' own names: ``fused`` (``Model.decode_fused``) and
# ``chunk`` (``Model.prefill_chunk``)
PROGRAM = r"^jit_fused\("


def read(run):
    steps = run.traced_steps()
    if not steps or run.trace is None:
        return None
    t0, t1 = run.traced_ns
    dev_s = sum(trace_reduce.module_time(d, PROGRAM, t0, t1)
                for d in run.devices()) / 1e9
    if dev_s <= 0:
        return None
    flops = sum(counting.decode_model_flops(run.dims, g)
                for s in steps for g in s.decode_rows)
    return 100.0 * flops / (dev_s * run.peaks.flops_per_s)
