"""Kernels (``kernels/ragged_prefill``): the least time of every chunked
prefill attention call inside the traced window (one per layer, per
chunk), over the kernel's device time, in %.  Least time as for the
decode kernel, over live query rows and the causal horizon.  Moves
``ttft_p95_ms``."""

from bench import counting, trace_reduce

# neither pallas_call is named yet: the kernel is the one Mosaic custom
# call inside the program that runs it
PROGRAM = r"^jit_chunk\("
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    chunks = run.traced_chunks()
    if not chunks or run.trace is None:
        return None
    t0, t1 = run.traced_ns
    dev_s = sum(o.dur for d in run.devices() for o in
                trace_reduce.kernel_ops(d, PROGRAM, KERNEL, t0, t1)) / 1e9
    if dev_s <= 0:
        return None
    least = sum(counting.ragged_prefill_call(run.dims, c["start"], c["qlen"])
                .least_s(run.peaks) for c in chunks) * run.dims.layers
    return 100.0 * least / dev_s
