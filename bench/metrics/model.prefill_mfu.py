"""Model step (``prefill_chunk``): model FLOPs of the live prompt tokens
of the chunks inside the traced window, over the device time of the
prefill programs times the chip's bf16 peak, in %.  Moves
``ttft_p95_ms``."""

from bench import counting, trace_reduce

# the jitted functions' own names: ``fused`` (``Model.decode_fused``) and
# ``chunk`` (``Model.prefill_chunk``)
PROGRAM = r"^jit_chunk\("


def read(run):
    chunks = run.traced_chunks()
    if not chunks or run.trace is None:
        return None
    t0, t1 = run.traced_ns
    dev_s = sum(trace_reduce.module_time(d, PROGRAM, t0, t1)
                for d in run.devices()) / 1e9
    if dev_s <= 0:
        return None
    plen = {r.idx: r.prompt_len for r in run.records}
    flops = sum(counting.prefill_model_flops(
                    run.dims, c["start"], c["qlen"],
                    c["start"] + c["qlen"] == plen[c["rid"]])
                for c in chunks)
    return 100.0 * flops / (dev_s * run.peaks.flops_per_s)
