"""Engine admission: median over requests of (start of its first
``prefill-chunk`` span - due time), for the requests whose first chunk ran
while the program's span tracer was attached.  Moves ``ttft_p95_ms``."""

import statistics


def read(run):
    due = {r.idx: r.due for r in run.records}
    waits = [c["ts"] - due[c["rid"]] for c in run.traced_chunks()
             if c["start"] == 0 and c["rid"] in due]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
