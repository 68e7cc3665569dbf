"""Ragged continuous-batching serving engine with serializable KV sessions.

Real execution path (works on one CPU device with a reduced model; on a pod
each width-w place holds a compiled executable pair):

* requests arrive with prompt tokens; **any free slot admits any queued
  prompt** regardless of length or current batch occupancy — prefill runs
  per request and its KV cache is inserted into the slot's rows of the
  batch cache (``Model.insert_session``);
* every engine step decodes a **chunk of ``decode_chunk`` tokens** for the
  whole active batch at **per-slot positions** (each slot masks/writes at
  its own position, so a slot admitted mid-flight decodes next to slots
  deep into generation).  The default path is ``Model.decode_fused``: the
  cache is *donated* into the jit (updated in place — no per-token copy of
  every layer's KV), greedy sampling runs on device, and ``cur_token`` /
  ``pos`` stay device-resident between chunks — the only host transfer per
  step is the ``(B, k)`` block of token ids.  A slot that reaches its
  ``max_new`` (or the cache edge) mid-chunk keeps only the tokens up to
  that point; the surplus the chunk decoded past it is truncated.
  ``fused=False`` keeps the legacy per-token path (undonated
  ``Model.decode_jit`` + host argmax) for A/B benchmarking;
* finished sequences (max_new reached) free their slots immediately;
* a live request can leave the engine as a :class:`Session`
  (``export_session``) — tokens, position, and its KV/state slice pulled to
  host numpy — and resume on another engine (``import_session``), which is
  how the fleet gateway drains a quarantined replica without killing its
  in-flight work;
* the :class:`ElasticServeScheduler` is consulted per prefill (critical) and
  per decode batch (non-critical) so the PTT learns group/width latencies —
  on one device the decision is degenerate but the full control path runs.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from ..models import Model
from ..obs import NULL_TRACER
from .scheduler import ElasticServeScheduler


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,)
    max_new: int
    tenant: int | str = 0        # fair-shedding bucket (SLOPolicy weights)
    extras: dict = dataclasses.field(default_factory=dict)
                                 # extra prefill inputs without the batch
                                 # axis (e.g. vlm "image_embeds")
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_first: float | None = None   # wall time the first token was produced
                                   # (stamped at prefill, so fleet TTFT is
                                   # not inflated by other admissions)
    t_admit: float | None = None   # wall time the engine started prefill
                                   # (under chunked admission, the start of
                                   # the request's first chunk): t_first -
                                   # t_admit is a pure service sample, free
                                   # of engine-queue wait


@dataclasses.dataclass
class Session:
    """A live request frozen for transport: the Request object itself (so
    the client's handle keeps accumulating tokens after migration), its
    decode position, the next input token, and its cache slice as host
    numpy arrays (``Model.extract_session``)."""
    req: Request
    pos: int
    cur_token: int
    cache: dict
    trace: dict | None = None    # trace context ({"trace_id": ...}) — the
                                 # request's causal identity rides the wire
                                 # so the importing engine's tracer can
                                 # continue the same timeline (wire v2's
                                 # optional "trace" key; None on v1 decode)
    prefilled: int | None = None  # None = prefill complete (a decode
                                  # session); else the number of prompt
                                  # tokens already consumed — a mid-prefill
                                  # export whose cache holds only those rows
                                  # (``cur_token`` is meaningless until the
                                  # remaining chunks run; wire v3's optional
                                  # "prefilled" key)
    delivery: tuple | None = None  # (origin, rid, epoch) delivery id the
                                   # shipping gateway stamped: adoption
                                   # dedups on it so a duplicated/retried
                                   # ship never double-adopts (wire v4's
                                   # optional "delivery" key)


@dataclasses.dataclass
class _Prefill:
    """An in-progress chunked prefill: the request plus its own growing
    (L, 1, ..., max_seq) device cache, donated back into the jit every
    chunk.  Lives outside the batch slots — a 32k prompt prefilling in
    chunks never blocks a decode slot."""
    req: Request
    cache: dict
    consumed: int = 0            # prompt tokens already in the cache
    logits = None                # last chunk's (1, 1, V) logits
    t_start: float | None = None  # first chunk wall time (prefill span)


class ServeEngine:
    def __init__(self, model: Model, params, max_batch: int, max_seq: int,
                 num_groups: int = 1, decode_chunk: int = 1,
                 fused: bool = True, role: str = "both",
                 prefill_chunk_tokens: int = 0):
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"unknown role {role!r}")
        self.model = model
        self.params = params
        # the engine lives where its weights do: caches and device-resident
        # tok/pos go to the device ``params`` are committed to, so replica i
        # built over ``jax.device_put(params, jax.devices()[i])`` runs on
        # chip i.  Params sharded over several devices, or none at all,
        # leave the device unset: allocation keeps JAX's default placement
        leaves = jax.tree.leaves(params)
        devices = leaves[0].devices() if leaves else set()
        self.device = next(iter(devices)) if len(devices) == 1 else None
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.decode_chunk = max(int(decode_chunk), 1)
        self.fused = fused
        # disaggregation surface: ``role`` is the replica's specialization
        # (a scheduling preference the gateway routes by — the engine stays
        # fully capable either way).  ``prefill_chunk_tokens`` > 0 admits
        # prompts through ``Model.prefill_chunk`` in fixed-size chunks that
        # interleave with decode steps instead of one whole-prompt dispatch
        # (falls back to whole-prompt prefill for families without a
        # chunkable prefill).
        self.role = role
        self.prefill_chunk_tokens = max(int(prefill_chunk_tokens), 0)
        # chaos surface: a crashed engine serves nothing until restart()
        # (see crash() — fault injection / process death stand-in)
        self.crashed = False
        self.scheduler = ElasticServeScheduler(num_groups)
        self.queue: deque[Request] = deque()
        self.sessions_in: deque[Session] = deque()   # imported, not yet slotted
        self.prefilling: deque[_Prefill] = deque()   # chunked prefills in
                                                     # flight (no slot held)
        self._prefill_ready: deque[tuple[Request, int, dict]] = deque()
                                 # chunk-prefilled, waiting for a free slot
                                 # (req, next_token, device cache)
        self.active: list[Request | None] = [None] * max_batch
        self.cache = None
        self.pos = np.zeros(max_batch, dtype=np.int32)
        self.cur_token = np.zeros((max_batch, 1), dtype=np.int32)
        # device-resident mirrors of cur_token/pos for the fused path: they
        # ride the decode outputs between chunks and are re-uploaded from
        # the host arrays only after a slot-changing event (admission,
        # finish, export) marks them dirty
        self._dev_tok = None
        self._dev_pos = None
        self._dev_dirty = True
        # the Model owns the jitted decodes: replicas sharing a Model share
        # the compiled executables, and they die with the Model
        self._decode = model.decode_jit
        self._decode_fused = model.decode_fused
        # fleet surface (router/gateway): called with each step's *decode*
        # latency normalized **per token** (elapsed / decode_chunk), so the
        # interference detector's signal stays comparable across replicas
        # running different chunk sizes (admission/prefill excluded — the
        # detector needs a homogeneous per-replica signal, and an
        # admission-heavy step would read as a latency spike on a healthy
        # replica).  Steps that run no decode (idle, or every admission
        # finished at prefill) leave the hook uncalled and
        # last_step_latency untouched.
        self.on_step_latency = None
        self.last_step_latency = 0.0
        # chunked prefill reports to its OWN signal — never
        # ``on_step_latency``: the interference detector's fast/baseline
        # tables need a homogeneous per-replica decode signal, and a
        # long-prompt prefill burst folded into it would read as a latency
        # spike (false quarantine) on a healthy replica
        self.on_prefill_latency = None
        self.last_prefill_chunk_latency = 0.0
        # disaggregation hook: when set (prefill-role replicas), a request
        # whose prefill just completed is frozen into a Session straight
        # off its prefill cache and handed to the callback — it never takes
        # a decode slot here (the fused prefill+admit path: the gateway
        # ships it to the decode-best replica)
        self.on_prefill_complete = None
        # observability (attach_obs): NULL_TRACER/no registry by default —
        # the decode hot path pays one `tracer.enabled` check per chunk
        self.tracer = NULL_TRACER
        self.metrics = None
        self.obs_name = "engine"
        self._served = 0         # requests finished on this engine
        self._exports = 0        # sessions migrated out
        self._imports = 0        # sessions migrated in
        self._m_served = self._m_tokens = None
        self._m_exports = self._m_imports = None
        self._h_prefill = self._h_step = self._h_prefill_chunk = None
        self._g_util = self._g_queue = None

    # -- observability -----------------------------------------------------
    def attach_obs(self, tracer=None, metrics=None,
                   name: str | None = None) -> None:
        """Attach a :class:`~repro.obs.SpanTracer` and/or
        :class:`~repro.obs.MetricRegistry`.  ``name`` labels this engine's
        series and is its span track.  Metric children are resolved once
        here so the decode loop pays a float add, not a registry lookup."""
        if name is not None:
            self.obs_name = name
        if tracer is not None:
            self.tracer = tracer
        if metrics is not None:
            self.metrics = metrics
            e = self.obs_name
            self._m_served = metrics.counter(
                "serve_requests_served_total",
                "Requests finished on this engine", engine=e)
            self._m_tokens = metrics.counter(
                "serve_decode_tokens_total",
                "Tokens decoded (batch slots x chunk)", engine=e)
            self._m_exports = metrics.counter(
                "serve_sessions_exported_total",
                "Live sessions migrated out", engine=e)
            self._m_imports = metrics.counter(
                "serve_sessions_imported_total",
                "Live sessions migrated in", engine=e)
            self._h_prefill = metrics.histogram(
                "serve_prefill_seconds", "Per-request prefill wall time",
                engine=e)
            self._h_step = metrics.histogram(
                "serve_decode_step_seconds",
                "Decode latency per token (elapsed / chunk)", engine=e)
            self._h_prefill_chunk = metrics.histogram(
                "serve_prefill_chunk_seconds",
                "Per-chunk prefill wall time (chunked admission)",
                engine=e, role=self.role)
            # point-in-time gauges refreshed each step so a sampling
            # TimeSeriesStore sees the occupancy/backlog trajectory
            self._g_util = metrics.gauge(
                "serve_utilization",
                "Fraction of batch slots occupied", engine=e)
            self._g_queue = metrics.gauge(
                "serve_queue_depth",
                "Requests queued but not slotted", engine=e)

    def stats(self) -> dict:
        """Counter facade with the unified cross-scale key names
        (:data:`repro.obs.CANONICAL_STATS`) plus engine-local detail."""
        return {
            "requests_served": self._served,
            "requests_shed": 0,          # engines never shed; the router does
            "sessions_migrated": self._exports + self._imports,
            "queue_depth": self.pending(),
            "sessions_exported": self._exports,
            "sessions_imported": self._imports,
            "active": self.active_count(),
            "utilization": self.utilization(),
            "role": self.role,
            "crashed": self.crashed,
            "prefilling": len(self.prefilling) + len(self._prefill_ready),
        }

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    # -- crash / restart (fault injection surface) -------------------------
    def crash(self) -> None:
        """Simulate process death: every piece of volatile state — queued
        requests, in-flight prefills, imported sessions, the batch cache,
        all active slots — is lost, exactly as a real crash loses it.
        The gateway's recovery path (heartbeat death -> parked wire
        snapshots re-placed, unstarted work resubmitted) is what preserves
        requests, never engine state.  Idempotent."""
        self.crashed = True
        self.queue.clear()
        self.sessions_in.clear()
        self.prefilling.clear()
        self._prefill_ready.clear()
        self.active = [None] * self.max_batch
        self.cache = None
        self.pos[:] = 0
        self.cur_token[:] = 0
        self._dev_tok = None
        self._dev_pos = None
        self._dev_dirty = True

    def restart(self) -> None:
        """Bring a crashed engine back empty (a replacement process with
        the same weights): it can accept work again, holds none.  Work
        submitted while the engine was dead is discarded here — a fresh
        process has an empty queue; the gateway's crash recovery already
        re-homed anything it was tracking."""
        self.queue.clear()
        self.sessions_in.clear()
        self.crashed = False

    # -- non-blocking fleet surface ----------------------------------------
    def pending(self) -> int:
        """Requests queued (fresh, imported sessions, chunked prefills in
        flight, or prefilled-and-waiting) but not slotted."""
        return (len(self.queue) + len(self.sessions_in)
                + len(self.prefilling) + len(self._prefill_ready))

    def active_count(self) -> int:
        return sum(r is not None for r in self.active)

    def utilization(self) -> float:
        """Fraction of batch slots occupied (0.0 = idle replica)."""
        return self.active_count() / self.max_batch

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.active) if r is None]

    def _zeros_cache(self, batch: int) -> dict:
        """A zeroed ``(batch, max_seq)`` cache on this engine's device."""
        spec = self.model.cache_spec(batch, self.max_seq)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=self.device), spec)

    def _ensure_cache(self) -> None:
        if self.cache is None:
            self.cache = self._zeros_cache(self.max_batch)

    def _chunking(self) -> bool:
        """Whether chunked prefill admission is live on this engine."""
        return (self.prefill_chunk_tokens > 0
                and self.model.prefill_chunk is not None)

    def _slot_in(self, slot: int, req: Request, next_tok: int,
                 cache) -> None:
        """Install a freshly-prefilled request into a batch slot (its cache
        may be a whole-prompt prefill cache or a chunked (1, max_seq)
        cache — ``insert_session`` handles both device-side, no host
        round trip)."""
        self._ensure_cache()
        with self.tracer.track_span("engine.insert", self.obs_name, slot=slot):
            self.cache = self.model.insert_session(self.cache, slot, cache)
        self.active[slot] = req
        self.pos[slot] = len(req.prompt)
        self.cur_token[slot, 0] = next_tok
        self._dev_dirty = True

    def _complete_prefill(self, req: Request, next_tok: int, cache) -> bool:
        """Shared prefill epilogue (whole-prompt and chunked): stamp the
        first token, then finish, hand off, or return False so the caller
        slots the request locally.

        The handoff branch is the fused prefill+admit path: when
        ``on_prefill_complete`` is set (prefill-role replicas), the live
        session is frozen **straight off the prefill cache** — no batch
        slot, no ``insert_session`` dispatch, no decode ever runs here —
        and handed to the gateway, which ships it to the decode-best
        replica."""
        req.out_tokens.append(next_tok)
        req.t_first = time.perf_counter()
        if len(req.out_tokens) >= req.max_new:
            req.done = True          # finished at prefill: no slot used
            self._finish(req)
            return True
        if self.on_prefill_complete is not None:
            sess = Session(
                req=req, pos=len(req.prompt), cur_token=next_tok,
                cache=self.model.extract_session(cache, 0, len(req.prompt)))
            self._exports += 1
            if self._m_exports is not None:
                self._m_exports.inc()
            if self.tracer.enabled:
                tid = self.tracer.trace_for(req.rid)
                if tid is not None:
                    sess.trace = {"trace_id": tid}
                    self.tracer.instant("prefill-handoff", tid,
                                        self.obs_name, pos=sess.pos)
            self.on_prefill_complete(sess)
            return True
        return False

    def _admit(self) -> int:
        """Slot what is ready and start queued prompts; returns how many
        requests moved (slotted, or started prefilling)."""
        # ragged continuous batching: any free slot takes any queued prompt
        # (chunk-prefilled requests first — their cache is already device
        # resident — then imported sessions, whose prefill was paid on the
        # engine they came from)
        slots = self._free_slots()
        moved = 0
        while slots and self._prefill_ready:
            req, next_tok, cache = self._prefill_ready.popleft()
            self._slot_in(slots.pop(0), req, next_tok, cache)
            moved += 1
        while slots and self.sessions_in:
            self._install_session(slots.pop(0), self.sessions_in.popleft())
            moved += 1
        while self.queue:
            chunkable = self._chunking() and not self.queue[0].extras
            if chunkable:
                # chunked admission holds no slot: the prompt prefills in
                # its own cache (one chunk per step, between decode chunks)
                # and claims a slot — or ships — only when done; its
                # t_admit is stamped when its first chunk runs
                if len(self.prefilling) >= self.max_batch:
                    break
                req = self.queue.popleft()
                self.prefilling.append(
                    _Prefill(req=req, cache=self._zeros_cache(1)))
                moved += 1
                continue
            if not slots and self.on_prefill_complete is None:
                break                # whole-prompt path needs a slot unless
                                     # every completion hands off
            req = self.queue.popleft()
            moved += 1
            t0 = time.perf_counter()
            req.t_admit = t0
            d = self.scheduler.schedule_prefill(len(req.prompt))
            batch = {"tokens": np.array(req.prompt, np.int32)[None, :]}
            for name, val in req.extras.items():
                batch[name] = np.array(val)[None]
            batch = jax.device_put(batch, self.device)
            logits, cache = self.model.prefill(self.params, batch)
            next_tok = int(jnp.argmax(logits[0, -1]))
            prefill_dur = time.perf_counter() - t0
            self.scheduler.record(d, prefill_dur, time.perf_counter())
            if self.tracer.enabled:
                tid = self.tracer.trace_for(req.rid)
                if tid is not None:
                    self.tracer.complete(
                        "prefill", tid, self.obs_name,
                        ts=t0, dur=prefill_dur, prompt_len=len(req.prompt))
            if self._h_prefill is not None:
                self._h_prefill.observe(prefill_dur)
            if self._complete_prefill(req, next_tok, cache):
                continue             # finished at prefill or handed off
            self._slot_in(slots.pop(0), req, next_tok, cache)
        return moved

    def _advance_prefill(self) -> None:
        """Run ONE prefill chunk for the oldest in-flight chunked prefill —
        called once per engine step, so a long prompt prefills incrementally
        between decode chunks instead of blocking them.  Chunk latency
        reports to ``on_prefill_latency`` / ``serve_prefill_chunk_seconds``
        (its own signal), never to the decode step hook."""
        if not self.prefilling:
            return
        pf = self.prefilling[0]
        # host-side prompt tokens, never a device value — no sync happens
        prompt = np.asarray(pf.req.prompt)  # analysis: allow-host-sync(prompt is host numpy, no device transfer)
        C = self.prefill_chunk_tokens
        qlen = min(C, len(prompt) - pf.consumed)
        t0 = time.perf_counter()
        if pf.t_start is None:
            pf.t_start = t0
        if pf.consumed == 0:
            pf.req.t_admit = t0      # its service starts with this chunk
        with self.tracer.track_span("engine.prefill.dispatch", self.obs_name):
            d = self.scheduler.schedule_prefill(qlen)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :qlen] = prompt[pf.consumed:pf.consumed + qlen]
            tokens, start, live = jax.device_put(
                (chunk, np.array([pf.consumed], np.int32),
                 np.array([qlen], np.int32)), self.device)
            logits, pf.cache = self.model.prefill_chunk(
                self.params, tokens, pf.cache, start, live)
        pf.logits = logits
        pf.consumed += qlen
        done = pf.consumed >= len(prompt)
        if done:
            with self.tracer.track_span("engine.prefill.sync", self.obs_name):
                next_tok = int(jnp.argmax(logits[0, -1]))  # chunk's host sync
        dur = time.perf_counter() - t0
        self.scheduler.record(d, dur, time.perf_counter())
        self.last_prefill_chunk_latency = dur
        if self._h_prefill_chunk is not None:
            self._h_prefill_chunk.observe(dur)
        if self.tracer.enabled:
            tid = self.tracer.trace_for(pf.req.rid)
            if tid is not None:
                self.tracer.complete("prefill-chunk", tid, self.obs_name,
                                     ts=t0, dur=dur, tokens=qlen,
                                     consumed=pf.consumed)
        if self.on_prefill_latency is not None:
            self.on_prefill_latency(dur)
        if done:
            self.prefilling.popleft()
            if self._h_prefill is not None:
                self._h_prefill.observe(time.perf_counter() - pf.t_start)
            if not self._complete_prefill(pf.req, next_tok, pf.cache):
                self._prefill_ready.append((pf.req, next_tok, pf.cache))

    def _finish(self, req: Request) -> None:
        """Bookkeep one finished request (counter + optional instant)."""
        self._served += 1
        if self._m_served is not None:
            self._m_served.inc()
        if self.tracer.enabled:
            self.tracer.instant("finish", self.tracer.trace_for(req.rid),
                                self.obs_name, tokens=len(req.out_tokens))

    # -- session migration -------------------------------------------------
    def export_session(self, rid: int) -> Session:
        """Freeze an active request into a transportable Session and free
        its slot.  Raises KeyError if ``rid`` is not active (still queued
        requests are moved by re-routing the Request itself)."""
        for slot, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                pos = int(self.pos[slot])
                sess = Session(
                    req=req, pos=pos, cur_token=int(self.cur_token[slot, 0]),
                    cache=self.model.extract_session(self.cache, slot, pos))
                self.active[slot] = None
                self.pos[slot] = 0
                self.cur_token[slot, 0] = 0
                self._dev_dirty = True
                self._exports += 1
                if self._m_exports is not None:
                    self._m_exports.inc()
                if self.tracer.enabled:
                    tid = self.tracer.trace_for(rid)
                    if tid is not None:      # sampled-out rids carry none
                        sess.trace = {"trace_id": tid}
                        self.tracer.instant("migrate-out", tid,
                                            self.obs_name, pos=pos)
                return sess
        raise KeyError(f"rid {rid} is not active on this engine")

    def export_prefill(self, rid: int) -> Session:
        """Freeze an in-progress chunked prefill into a transportable
        partial Session (``prefilled`` = prompt tokens already consumed;
        the cache holds exactly those rows).  The importing engine resumes
        the remaining chunks — prefill work done so far is never redone.
        Raises KeyError if ``rid`` is not mid-prefill here."""
        for i, pf in enumerate(self.prefilling):
            if pf.req.rid == rid:
                del self.prefilling[i]
                k = pf.consumed
                sess = Session(
                    req=pf.req, pos=k, cur_token=0,
                    cache=self.model.extract_session(pf.cache, 0, k),
                    prefilled=k)
                self._exports += 1
                if self._m_exports is not None:
                    self._m_exports.inc()
                if self.tracer.enabled:
                    tid = self.tracer.trace_for(rid)
                    if tid is not None:
                        sess.trace = {"trace_id": tid}
                        self.tracer.instant("migrate-out", tid,
                                            self.obs_name, pos=k,
                                            prefilled=k)
                return sess
        raise KeyError(f"rid {rid} is not mid-prefill on this engine")

    def can_hold(self, pos: int, remaining: int) -> bool:
        """Whether a session at ``pos`` with ``remaining`` tokens to decode
        fits this engine without truncation — the one fit rule shared by
        ``import_session`` and migration feasibility pre-checks."""
        return not self.crashed and pos + remaining <= self.max_seq - 1

    def import_session(self, sess: Session, strict: bool = True) -> None:
        """Accept a migrated session; it resumes decoding at the next
        ``step`` with a free slot (ahead of fresh prompts).

        ``strict`` (default) also requires the engine to hold the session's
        *remaining token budget* — a smaller-max_seq replica would otherwise
        silently truncate the generation, breaking token identity across
        the migration.  ``strict=False`` is for re-parking a session on its
        source engine, where truncation semantics are unchanged."""
        if self.crashed:
            raise ValueError("engine is crashed; restart() before imports")
        if sess.prefilled is not None:
            self._import_partial(sess)
            return
        if sess.pos >= self.max_seq - 1:
            raise ValueError(
                f"session at pos {sess.pos} does not fit max_seq "
                f"{self.max_seq}")
        remaining = max(sess.req.max_new - len(sess.req.out_tokens), 0)
        if strict and not self.can_hold(sess.pos, remaining):
            raise ValueError(
                f"session at pos {sess.pos} with {remaining} tokens to go "
                f"would truncate at max_seq {self.max_seq}")
        self._imports += 1
        if self._m_imports is not None:
            self._m_imports.inc()
        if sess.trace is not None:
            # continue the request's original timeline: the carried trace
            # id wins over anything this tracer would mint for the rid
            self.tracer.adopt(sess.req.rid, sess.trace["trace_id"])
        if self.tracer.enabled:
            self.tracer.instant("migrate-in",
                                self.tracer.trace_for(sess.req.rid),
                                self.obs_name, pos=sess.pos)
        self.sessions_in.append(sess)

    def _import_partial(self, sess: Session) -> None:
        """Adopt a mid-prefill session: rebuild the chunked-prefill state
        (its cache rows land in a fresh per-request device cache) and
        resume the remaining chunks from ``sess.prefilled``."""
        if not self._chunking():
            raise ValueError(
                "partial-prefill session needs a chunked-prefill engine "
                "(prefill_chunk_tokens > 0 and a chunkable model family)")
        plen = len(sess.req.prompt)
        if not self.can_hold(plen, max(sess.req.max_new, 1)):
            raise ValueError(
                f"prompt of {plen} with {sess.req.max_new} to decode does "
                f"not fit max_seq {self.max_seq}")
        self._imports += 1
        if self._m_imports is not None:
            self._m_imports.inc()
        if sess.trace is not None:
            self.tracer.adopt(sess.req.rid, sess.trace["trace_id"])
        if self.tracer.enabled:
            tid = self.tracer.trace_for(sess.req.rid)
            if tid is not None:
                self.tracer.instant("migrate-in", tid, self.obs_name,
                                    pos=sess.pos, prefilled=sess.prefilled)
        cache = self.model.insert_session(self._zeros_cache(1), 0,
                                          sess.cache)
        self.prefilling.append(
            _Prefill(req=sess.req, cache=cache, consumed=sess.prefilled))

    def export_session_wire(self, rid: int) -> bytes:
        """:meth:`export_session` encoded with the versioned session wire
        format (:mod:`repro.region.wire`) — the byte form that crosses
        process/WAN boundaries."""
        from ..region.wire import encode_session   # avoid import cycle
        return encode_session(self.export_session(rid))

    def import_session_wire(self, data: bytes, strict: bool = True) -> None:
        """Accept a session shipped as wire bytes (the far end of
        :meth:`export_session_wire`); validation errors raise
        :class:`~repro.region.wire.WireFormatError` before any state is
        touched."""
        from ..region.wire import decode_session   # avoid import cycle
        self.import_session(decode_session(data), strict=strict)

    def active_pos(self, rid: int) -> int | None:
        """Decode position of an active request (None if not active) —
        lets a migration planner check placement feasibility without
        paying for an export."""
        for slot, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                return int(self.pos[slot])
        return None

    def drain_queue(self) -> list[Request]:
        """Remove and return all queued-but-unstarted requests (gateway
        re-routes them when this replica is quarantined).  In-flight
        chunked prefills are aborted back to plain requests — no token has
        been emitted yet, so restarting the prefill elsewhere is
        correctness-free (a planner that wants to keep the partial work
        uses :meth:`export_prefill` instead)."""
        out = list(self.queue) + [pf.req for pf in self.prefilling]
        self.queue.clear()
        self.prefilling.clear()
        return out

    def drain_sessions(self) -> list[Session]:
        """Remove and return imported-but-not-yet-slotted sessions — a
        quarantined replica must not decode them even once.  Requests that
        finished a chunked prefill but are still waiting for a slot leave
        as full sessions (their first token is already stamped)."""
        out = list(self.sessions_in)
        self.sessions_in.clear()
        for req, next_tok, cache in self._prefill_ready:
            out.append(Session(
                req=req, pos=len(req.prompt), cur_token=next_tok,
                cache=self.model.extract_session(cache, 0,
                                                 len(req.prompt))))
        self._prefill_ready.clear()
        return out

    def _install_session(self, slot: int, sess: Session) -> None:
        self._ensure_cache()
        with self.tracer.track_span("engine.insert", self.obs_name, slot=slot):
            self.cache = self.model.insert_session(self.cache, slot,
                                                   sess.cache)
        self.active[slot] = sess.req
        self.pos[slot] = sess.pos
        self.cur_token[slot, 0] = sess.cur_token
        self._dev_dirty = True

    # -- decode loop ---------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: admit + decode one ``decode_chunk``-token
        chunk for the batch at per-slot positions.  Returns number of active
        sequences.

        Fused path (default): one ``Model.decode_fused`` dispatch decodes
        the whole chunk with the cache donated (in-place update) and greedy
        sampling on device; the ``(B, k)`` token ids are the chunk's single
        host transfer.  Slots that finish mid-chunk keep only their tokens
        up to the finish; the surplus the chunk decoded past it is
        truncated (and the freed slot is re-synced to device via the dirty
        flag before the next chunk).  ``last_step_latency`` and the
        ``on_step_latency`` hook report the decode latency **per token**
        (elapsed / chunk), keeping the interference signal comparable
        across chunk sizes.

        With a tracer attached, the call is an ``engine.step`` span on this
        engine's track (args: the batch and backlog at its start) holding
        one span per phase: ``engine.admit``, ``engine.insert``,
        ``engine.prefill.dispatch``/``.sync``, ``engine.upload``,
        ``engine.decode.dispatch``/``.sync`` and ``engine.harvest``."""
        if self.crashed:
            return 0                 # a dead process steps nothing
        if self.tracer.enabled:
            with self.tracer.track_span("engine.step", self.obs_name,
                                        **self._step_state()):
                return self._step()
        return self._step()

    def _step_state(self) -> dict:
        """The batch and the backlog as a step starts: occupied slots of
        ``capacity``, prompts queued and prefilling, and the prompt tokens
        of both not yet prefilled; and the ``device`` (its id) the engine
        runs on, where it runs on one."""
        backlog = (sum(len(r.prompt) for r in self.queue)
                   + sum(len(pf.req.prompt) - pf.consumed
                         for pf in self.prefilling))
        state = {"active": self.active_count(), "capacity": self.max_batch,
                 "queued": len(self.queue),
                 "prefilling": len(self.prefilling) + len(self._prefill_ready),
                 "backlog_tokens": backlog}
        if self.device is not None:
            state["device"] = self.device.id
        return state

    def _step(self) -> int:
        with self.tracer.track_span("engine.admit", self.obs_name) as span:
            span["admitted"] = self._admit()
        self._advance_prefill()      # one chunk, timed on its own signal
        n_active = self.active_count()
        if self._g_util is not None:
            self._g_util.set(n_active / self.max_batch)
            self._g_queue.set(float(self.pending()))
        if n_active == 0:
            return 0
        d = self.scheduler.schedule_decode(group=0)
        t0 = time.perf_counter()
        if self._dev_dirty or self._dev_tok is None:
            # both paths keep cur_token/pos device-resident between steps;
            # this re-upload runs only after a slot-changing event
            # (admission, finish, export) marked them dirty
            with self.tracer.track_span("engine.upload", self.obs_name):
                self._dev_tok, self._dev_pos = jax.device_put(
                    (self.cur_token, self.pos), self.device)
            self._dev_dirty = False
        if self.fused:
            k = self.decode_chunk
            with self.tracer.track_span("engine.decode.dispatch",
                                        self.obs_name):
                toks_dev, self._dev_tok, self._dev_pos, self.cache = (
                    self._decode_fused(self.params, self._dev_tok,
                                       self._dev_pos, self.cache, k))
            # the chunk's ONE host sync: a (B, k) block of token ids
            with self.tracer.track_span("engine.decode.sync", self.obs_name):
                toks = np.asarray(toks_dev)  # analysis: allow-host-sync(the one sanctioned sync per decode chunk)
        else:
            # legacy per-step path (A/B baseline): undonated decode, but
            # cur_token/pos stay device-resident with the same dirty-resync
            # scheme as the fused path — argmax runs on device and only the
            # (B, 1) token ids cross to host, not the full logits row plus
            # a cur_token re-upload every step
            k = 1
            with self.tracer.track_span("engine.decode.dispatch",
                                        self.obs_name):
                logits, self.cache = self._decode(
                    self.params, self._dev_tok, self._dev_pos, self.cache)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(
                    jnp.int32)[:, None]
                self._dev_tok = nxt
                self._dev_pos = self._dev_pos + 1
            # the step's ONE host sync: the (B, 1) block of token ids
            with self.tracer.track_span("engine.decode.sync", self.obs_name):
                toks = np.asarray(nxt)  # analysis: allow-host-sync(the one sanctioned sync per legacy step)
        decode_elapsed = time.perf_counter() - t0
        self.scheduler.record(d, decode_elapsed, time.perf_counter())
        if self.tracer.enabled:
            # one span per active request per chunk, before the harvest
            # loop nulls finished slots — every request's timeline shows
            # the chunks that decoded it
            for req in self.active:
                if req is not None:
                    self.tracer.complete(
                        "decode-chunk", self.tracer.trace_for(req.rid),
                        self.obs_name, ts=t0, dur=decode_elapsed, tokens=k)
        with self.tracer.track_span("engine.harvest", self.obs_name):
            self._harvest(toks, k)
        if any(r is None for r in self.active):
            # keep idle slots' device pos pinned at 0: both paths advance
            # every slot's device pos unconditionally, so without this
            # re-sync a long-idle slot's garbage decode would creep across
            # the whole cache and end up attending (and, on TPU, DMA'ing)
            # all of Smax every chunk — two tiny int32 uploads per step
            # keep an idle slot at one read block in the kernel
            self._dev_dirty = True
        per_token = decode_elapsed / k
        self.last_step_latency = per_token
        if self._h_step is not None:
            self._h_step.observe(per_token)
            self._m_tokens.inc(n_active * k)
        if self.on_step_latency is not None:
            self.on_step_latency(per_token)
        return n_active

    def _harvest(self, toks: np.ndarray, k: int) -> None:
        """Append each active slot's tokens of the chunk; free the slots
        that finished (a slot's surplus chunk tokens are truncated)."""
        for i, req in enumerate(self.active):
            if req is None:
                continue
            for j in range(k):
                req.out_tokens.append(int(toks[i, j]))
                self.pos[i] += 1
                self.cur_token[i, 0] = int(toks[i, j])
                if (len(req.out_tokens) >= req.max_new
                        or self.pos[i] >= self.max_seq - 1):
                    req.done = True              # surplus chunk tokens (j+1
                    self.active[i] = None        # onward) are truncated
                    self.pos[i] = 0
                    self.cur_token[i, 0] = 0
                    self._dev_dirty = True
                    self._finish(req)
                    break

    def run_until_drained(self, max_steps: int = 10000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.pending():
                return
