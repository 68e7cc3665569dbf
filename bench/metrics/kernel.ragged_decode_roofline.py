"""Kernels (``kernels/ragged_decode``): the least time of every decode
attention call inside the traced window (one per layer, per position in
the decode chunk), over the kernel's device time, in %.  A call's least
time is the larger of its FLOPs over the bf16 peak and its bytes over
HBM bandwidth, on live KV rows only; at these shapes bytes bound it.
Moves ``tpot_p95_ms``."""

from bench import counting, trace_reduce

# neither pallas_call is named yet: the kernel is the one Mosaic custom
# call inside the program that runs it
PROGRAM = r"^jit_fused\("
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    steps = run.traced_steps()
    if not steps or run.trace is None:
        return None
    t0, t1 = run.traced_ns
    dev_s = sum(o.dur for d in run.devices() for o in
                trace_reduce.kernel_ops(d, PROGRAM, KERNEL, t0, t1)) / 1e9
    if dev_s <= 0:
        return None
    least = sum(counting.ragged_decode_call(run.dims, g).least_s(run.peaks)
                for s in steps for g in s.decode_rows) * run.dims.layers
    return 100.0 * least / dev_s
