"""End-to-end arithmetic and the open loop's bookkeeping, on a fake
system: TTFT runs from the due time, a request with no first token enters
the tail censored at its wait so far, failed requests miss the limits, and
only tokens emitted inside the window count."""

import time

import pytest

from bench import driver, stats, traffic

LIMITS = {"ttft_ms": {"base": 100.0, "per_1k_prompt_tokens": 50.0},
          "tpot_ms": 10.0}


def rec(idx, due, first=None, done=None, n=0, shed=False, plen=1000):
    return stats.Record(idx=idx, due=due, prompt_len=plen, max_new=n,
                        t_first=first, t_done=done, n_tokens=n, shed=shed)


def test_ttft_limit_grows_with_prompt():
    assert stats.ttft_limit_s(LIMITS, 2000) == pytest.approx(0.2)


def test_unanswered_request_enters_tail_at_its_wait():
    rs = [rec(i, float(i), first=i + 0.01, done=i + 0.1, n=10)
          for i in range(19)]
    rs.append(rec(19, 19.0))                      # never got a token
    out = stats.end_to_end(rs, 0.0, 20.0, 100.0, 0, LIMITS)
    ttfts = [0.01] * 19 + [81.0]
    assert out["ttft_p95_ms"] == pytest.approx(
        stats.percentile(ttfts, 95) * 1e3)
    assert out["ttft_p95_ms"] > 1000              # the censored wait shows
    assert out["failed"] == 1 and out["attempted"] == 20


def test_failed_requests_miss_and_window_selects_by_due():
    rs = [rec(0, 1.0, first=1.05, done=1.1, n=11),       # meets both
          rec(1, 2.0, first=2.05, done=2.5, n=11),       # tpot 45 ms: miss
          rec(2, 3.0, shed=True),                         # shed: miss
          rec(3, 4.0, first=4.01, done=None, n=3),       # unfinished: miss
          rec(4, 50.0, first=50.01, done=50.02, n=2)]    # due after window
    out = stats.end_to_end(rs, 0.0, 10.0, 20.0, 30, LIMITS)
    assert out["attempted"] == 4 and out["failed"] == 2
    assert out["slo_attained"] == pytest.approx(0.25)
    assert out["tokens_per_s"] == pytest.approx(3.0)
    assert out["tpot_p95_ms"] == pytest.approx(
        stats.percentile([5.0, 45.0], 95))


class FakeEngine:
    """Emits one token per live request per step; steps take ``dt``."""

    def __init__(self, dt=0.002):
        self.dt, self.live = dt, []

    def submit(self, req):
        self.live.append(req)

    def step(self):
        time.sleep(self.dt)
        for r in self.live:
            if r.t_first is None:
                r.t_first = time.perf_counter()
            r.out_tokens.append(1)
            r.done = len(r.out_tokens) >= r.max_new
        self.live = [r for r in self.live if not r.done]

    def active_count(self):
        return len(self.live)

    def pending(self):
        return 0


def test_open_loop_counts_only_tokens_inside_the_window():
    arrivals = [traffic.Arrival(i, 0.05 * i, 4, 20) for i in range(20)]
    eng = FakeEngine()
    loop = driver.OpenLoop(driver.System([eng]), arrivals, 0, 16)
    emitted = []

    def count():
        emitted.append(sum(len(r.out_tokens) for r in loop.requests))

    win = loop.run(0.3, 0.4, 5.0, {"open": count, "close": count})
    assert win.drained
    # arrivals stop at the close (0.7 s): requests 0..13 are submitted
    total = sum(len(r.out_tokens) for r in loop.requests)
    assert loop.next == 14 and total == 14 * 20
    inside = win.tokens_close - win.tokens_open
    assert inside == emitted[1] - emitted[0]
    assert 0 < inside < total
    due = [r for r in loop.records if win.w_open <= r.due < win.w_close]
    assert 5 <= len(due) <= 10
    for r in due:
        assert r.t_first >= r.due and r.t_done >= r.t_first
        assert r.n_tokens == 20
    assert len(loop.lateness) == 14 and min(loop.lateness) >= 0
