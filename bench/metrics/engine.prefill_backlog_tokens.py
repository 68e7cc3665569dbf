"""Engine admission (``serve/engine.py``): the mean over the
``engine.step`` spans inside the traced window of ``backlog_tokens``, the
prompt tokens queued or not yet prefilled as the step starts.  Moves
``ttft_p95_ms``."""

from bench import program_spans


def read(run):
    steps = program_spans.steps(run)
    if not steps:
        return None
    return sum(s.span.args["backlog_tokens"] for s in steps) / len(steps)
