"""Engine admission (``serve/engine.py``): the mean over the
``engine.step`` spans inside the traced window of ``active / capacity``,
the batch slots occupied as the step starts, in %.  Moves
``tokens_per_s``."""

from bench import program_spans


def read(run):
    steps = program_spans.steps(run)
    if not steps:
        return None
    return 100.0 * sum(s.span.args["active"] / s.span.args["capacity"]
                       for s in steps) / len(steps)
