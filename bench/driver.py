"""The system under test behind one interface, and the open-loop loop that
drives it.

A configuration with ``replicas: 1`` is one ``ServeEngine`` driven by
``submit``/``step``; with more, replica ``i`` is an engine on
``jax.devices()[i]`` behind a ``FleetGateway`` driven by ``submit``/``pump``.
The program receives only the generated requests.

One host thread drives everything.  Each loop turn submits every request
now due, then steps the system once; when the system holds no work the
loop sleeps until the next request is due.  With ``annotate`` on, every
step, submit and sleep is a ``jax.profiler.TraceAnnotation``, so these
spans share the device trace's clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import stats, traffic


def _annotation(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class System:
    """``engines`` alone, or behind ``gateway``."""

    def __init__(self, engines: list, gateway=None):
        self.engines = engines
        self.gateway = gateway

    def submit(self, req) -> tuple[bool, int | None]:
        """Hand ``req`` to the program: (shed, replica)."""
        if self.gateway is None:
            self.engines[0].submit(req)
            return False, 0
        from repro.router import Admission
        d = self.gateway.submit(req)
        return d.action is Admission.SHED, d.replica

    def step(self) -> None:
        if self.gateway is None:
            self.engines[0].step()
        else:
            self.gateway.pump()

    def idle(self) -> bool:
        busy = any(e.active_count() or e.pending() for e in self.engines)
        if self.gateway is not None:
            busy = busy or bool(self.gateway.held)
        return not busy

    def shed_rids(self) -> set[int]:
        if self.gateway is None:
            return set()
        return {r.rid for r in self.gateway.shed}


def build_system(config: dict, model_cfg, params_per_replica: list
                 ) -> System:
    """Engines (and a gateway) as the configuration states them, over the
    program's model configuration ``model_cfg``."""
    from repro.models import get_model
    from repro.serve import ServeEngine
    model = get_model(model_cfg)
    e = config["engine"]
    engines = [ServeEngine(model, p, e["max_batch"], e["max_seq"],
                           decode_chunk=e["decode_chunk"],
                           prefill_chunk_tokens=e["prefill_chunk_tokens"])
               for p in params_per_replica]
    if config["replicas"] == 1:
        return System(engines)
    from repro.router import FleetGateway, FleetRouter, SLOPolicy
    policy = {"default": SLOPolicy.default,
              "unlimited": SLOPolicy.unlimited}[config["admission"]]()
    router = FleetRouter(len(engines), slo=policy)
    return System(engines, FleetGateway(engines, router=router))


@dataclasses.dataclass
class Window:
    """Host-clock marks of one run (``perf_counter`` seconds)."""
    t0: float = 0.0              # schedule origin: request i is due t0 + due
    w_open: float = 0.0
    w_close: float = 0.0
    t_end: float = 0.0           # drain stopped
    tokens_open: int = 0
    tokens_close: int = 0
    drained: bool = False


class OpenLoop:
    """Plays a schedule into a :class:`System` and records what it saw."""

    def __init__(self, system: System, arrivals: list, seed: int,
                 vocab: int, annotate: bool = False):
        from repro.serve import Request
        self.system = system
        self.arrivals = arrivals
        self.annotation = _annotation(annotate)
        self.records = [stats.Record(idx=a.idx, due=a.due,
                                     prompt_len=a.prompt_len,
                                     max_new=a.max_new) for a in arrivals]
        self.requests = [Request(rid=a.idx,
                                 prompt=traffic.prompt_tokens(
                                     seed, a.idx, a.prompt_len, vocab),
                                 max_new=a.max_new) for a in arrivals]
        self.lateness: list[float] = []
        self.live: list[int] = []          # submitted, not yet done
        self.next = 0
        self.win = Window()

    def _tokens(self) -> int:
        return sum(len(r.out_tokens) for r in self.requests[:self.next])

    def _submit_due(self, now: float, stop_at: float) -> None:
        while (self.next < len(self.arrivals)
               and self.win.t0 + self.arrivals[self.next].due <= now
               and self.win.t0 + self.arrivals[self.next].due < stop_at):
            i = self.next
            rec, req = self.records[i], self.requests[i]
            rec.due = self.win.t0 + self.arrivals[i].due
            with self.annotation("bench.submit"):
                shed, replica = self.system.submit(req)
            rec.submitted = time.perf_counter()
            rec.replica = replica
            rec.shed = shed
            self.lateness.append(rec.submitted - rec.due)
            if not shed:
                self.live.append(i)
            self.next += 1

    def _harvest(self) -> None:
        now = time.perf_counter()
        still = []
        for i in self.live:
            req = self.requests[i]
            if req.done:
                rec = self.records[i]
                rec.t_done, rec.t_first = now, req.t_first
                rec.n_tokens = len(req.out_tokens)
            else:
                still.append(i)
        self.live = still

    def _step(self) -> None:
        with self.annotation("bench.step"):
            self.system.step()
        self._harvest()

    def run(self, steady_s: float, seconds: float, drain_s: float,
            hooks: dict | None = None) -> Window:
        """Arrivals from ``t0``; the window is ``[t0 + steady_s,
        + seconds)``; arrivals stop at its close, then the run drains for
        at most ``drain_s``.  ``hooks`` may hold ``open``/``close``
        callables, run at the first step boundary past that mark, and
        ``at``: (seconds into the window, callable) pairs in time order."""
        hooks = hooks or {}
        win = self.win
        win.t0 = time.perf_counter()
        w_open, w_close = win.t0 + steady_s, win.t0 + steady_s + seconds
        opened = False
        at = list(hooks.get("at", []))
        while True:
            now = time.perf_counter()
            if not opened and now >= w_open:
                opened = True
                win.w_open, win.tokens_open = now, self._tokens()
                if "open" in hooks:
                    hooks["open"]()
            while at and opened and now >= w_open + at[0][0]:
                at.pop(0)[1]()
            if now >= w_close:
                break
            self._submit_due(now, w_close)
            if self.system.idle():
                nxt = (win.t0 + self.arrivals[self.next].due
                       if self.next < len(self.arrivals) else w_close)
                with self.annotation("bench.sleep"):
                    time.sleep(max(min(nxt, w_close) - time.perf_counter(),
                                   0.0))
                continue
            self._step()
        win.w_close, win.tokens_close = time.perf_counter(), self._tokens()
        if "close" in hooks:
            hooks["close"]()
        # drain: every request due in the window gets until the deadline
        deadline = win.w_close + drain_s
        while self.live and time.perf_counter() < deadline:
            self._step()
        win.t_end = time.perf_counter()
        win.drained = not self.live
        for i in self.system.shed_rids():
            if i < len(self.records):
                self.records[i].shed = True
        for i in self.live:
            self.records[i].t_first = self.requests[i].t_first
            self.records[i].n_tokens = len(self.requests[i].out_tokens)
        return win


def warm_up(system: System, config: dict, vocab: int) -> None:
    """Compile every program this configuration's traffic drives, on every
    replica: ``prefill_chunk`` at ``(1, C)`` for one- and two-chunk
    prompts, admission into a slot, ``decode_fused`` at ``(max_batch, k)``
    and a slot's release.  The slot index is an operand of the admission
    copy, not a constant, so one admission compiles it for every slot: a
    cold run of the long-chat cell counted the same 18 compiles with all
    64 slots filled as with three."""
    from repro.serve import Request
    e = config["engine"]
    C, k = e["prefill_chunk_tokens"], e["decode_chunk"]
    rng = np.random.default_rng(0)
    for n, engine in enumerate(system.engines):
        reqs = [Request(rid=-(3 * n + i + 1),
                        prompt=rng.integers(0, vocab, plen).astype(np.int32),
                        max_new=2 * k + 1)
                for i, plen in enumerate((C // 2, C + 1, C // 2))]
        for r in reqs:
            engine.submit(r)
        for _ in range(10_000):
            engine.step()
            if engine.active_count() == 0 and not engine.pending():
                break
        if not all(r.done for r in reqs):
            raise RuntimeError(f"warm-up did not finish on replica {n}")
