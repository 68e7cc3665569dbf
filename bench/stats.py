"""End-to-end arithmetic over one run's request records.

Every request is timed from its *due* time in the open-loop schedule, not
from when the generator got round to submitting it, so a stall that delays
later submissions shows in their latency.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def percentile(samples, q: float) -> float:
    """Exact q-th percentile (q in [0, 100]) with linear interpolation;
    ``nan`` on no samples."""
    a = np.asarray(samples, dtype=float)
    return float(np.percentile(a, q)) if a.size else float("nan")


@dataclasses.dataclass
class Record:
    """What the harness saw of one request.  Times are ``perf_counter``
    seconds; ``t_first`` is the engine's own stamp of the first token,
    ``t_done`` the end of the step after which the request was finished."""
    idx: int
    due: float
    prompt_len: int
    max_new: int
    submitted: float | None = None
    t_first: float | None = None
    t_done: float | None = None
    n_tokens: int = 0
    shed: bool = False
    error: str | None = None
    replica: int | None = None

    @property
    def failed(self) -> bool:
        return self.shed or self.error is not None or self.t_done is None

    def ttft(self, t_end: float) -> float:
        """Due -> first token; a request with no first token enters at its
        wait so far (censored at ``t_end``)."""
        return (self.t_first if self.t_first is not None else t_end) - self.due

    def tpot(self) -> float | None:
        if self.t_done is None or self.t_first is None or self.n_tokens < 2:
            return None
        return (self.t_done - self.t_first) / (self.n_tokens - 1)


def ttft_limit_s(limits: dict, prompt_len: int) -> float:
    t = limits["ttft_ms"]
    return (t["base"] + t["per_1k_prompt_tokens"] * prompt_len / 1000) / 1e3


def met_limits(rec: Record, limits: dict, t_end: float) -> bool:
    if rec.failed:
        return False
    tpot = rec.tpot()
    return (rec.ttft(t_end) <= ttft_limit_s(limits, rec.prompt_len)
            and tpot is not None and tpot <= limits["tpot_ms"] / 1e3)


def end_to_end(records: list[Record], w_open: float, w_close: float,
               t_end: float, tokens_in_window: int, limits: dict) -> dict:
    """The four serving metrics over the requests due in the window.

    ``t_end`` is when the drain stopped: a request without a first token by
    then enters the TTFT tail at ``t_end - due``.  ``tokens_in_window`` is
    the output tokens emitted between the window's open and close."""
    due = [r for r in records if w_open <= r.due < w_close]
    ttfts = [r.ttft(t_end) for r in due]
    tpots = [t for t in (r.tpot() for r in due) if t is not None]
    met = sum(met_limits(r, limits, t_end) for r in due)
    return {
        "ttft_p95_ms": percentile(ttfts, 95) * 1e3,
        "tpot_p95_ms": percentile(tpots, 95) * 1e3,
        "tokens_per_s": tokens_in_window / (w_close - w_open),
        "slo_attained": met / len(due) if due else float("nan"),
        "attempted": len(due),
        "failed": sum(r.failed for r in due),
    }
