"""Prefill-decode disaggregation: role-specialized replicas, chunked
Pallas prefill, and PTT-routed KV session handoff.

The contract under test is token identity end to end: a request prefilled
on a prefill-specialized replica, shipped over the RSES wire format, and
decoded on a decode-specialized replica must emit exactly the greedy
stream a monolithic engine emits — on every model family, including a
session exported *mid-prefill-chunk* and resumed elsewhere.  Around that
core: the chunked Pallas prefill kernel vs its jnp oracle, the role
restrictions at the router, the separate prefill-chunk latency signal
(the interference detector must NOT see prompt chunks), RTT row aging,
and sampled tracing across the handoff."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.ragged_prefill import force_pallas, ragged_prefill_attention
from repro.kernels.ragged_prefill.ref import ragged_prefill_ref
from repro.models import get_model
from repro.obs import MetricRegistry, SpanTracer
from repro.region.router import RegionRouter
from repro.region.wire import (WIRE_VERSION, WireFormatError, decode_session,
                               encode_session, wire_header)
from repro.router.gateway import FleetGateway
from repro.router.router import FleetRouter
from repro.serve import Request, ServeEngine

# one representative arch per family with a decode path (test_sessions.py)
FAMILY_ARCHS = ("qwen2-0.5b", "granite-moe-1b-a400m", "mamba2-130m",
                "jamba-v0.1-52b", "llama-3.2-vision-90b")

MAX_NEW = 6


def _setup(arch, seed=0):
    cfg = get_config(arch, reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(seed))
    return cfg, m, params


def _request(cfg, rng, rid, plen=9, max_new=MAX_NEW):
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = np.asarray(
            jax.random.normal(jax.random.PRNGKey(7),
                              (cfg.n_image_tokens, cfg.d_model)))
    return Request(rid=rid, prompt=rng.integers(0, cfg.vocab, plen),
                   max_new=max_new, extras=extras)


def _clone(req, rid):
    return Request(rid=rid, prompt=req.prompt.copy(), max_new=req.max_new,
                   extras=dict(req.extras))


def _monolithic(m, params, req):
    e = ServeEngine(m, params, max_batch=2, max_seq=32)
    e.submit(req)
    e.run_until_drained(max_steps=200)
    assert req.done
    return list(req.out_tokens)


# ---------------------------------------------------------------------------
# chunked Pallas prefill kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Smax,T,Hq,Hkv,hd,bk", [
    (3, 32, 8, 8, 2, 16, 8),     # GQA, block-divisible cache
    (2, 19, 5, 6, 6, 8, 8),      # MHA, cache not a bk multiple
    (4, 24, 4, 4, 1, 8, 16),     # MQA
])
def test_ragged_prefill_kernel_matches_reference(B, Smax, T, Hq, Hkv, hd,
                                                 bk):
    """Op-level: chunked causal prefill attention over ragged per-slot
    (start, qlen) windows — Pallas (interpret mode) vs the dense jnp
    oracle, including zeroed padding rows past each slot's qlen."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Smax, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Smax, hd)), jnp.float32)
    start = jnp.asarray(rng.integers(0, Smax - T, B), jnp.int32)
    # mix live, partial, and fully-padded (qlen=0) slots
    qlen = jnp.asarray(([T, max(T - 2, 1), 0, T] * B)[:B], jnp.int32)
    ref = ragged_prefill_ref(q, k, v, start, qlen)
    with force_pallas():
        out = ragged_prefill_attention(q, k, v, start, qlen, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # padded rows are exact zeros in both paths
    for b in range(B):
        assert not np.asarray(out)[b, int(qlen[b]):].any()
    # and the default (CPU) route IS the reference
    got = ragged_prefill_attention(q, k, v, start, qlen)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_chunked_prefill_token_identity_vs_whole_prompt():
    """Model-level: consuming a prompt in fixed-size chunks through
    ``Model.prefill_chunk`` yields the same next token and the same greedy
    stream as the whole-prompt prefill path."""
    cfg, m, params = _setup("smollm-135m")
    assert m.prefill_chunk is not None
    rng = np.random.default_rng(5)
    ref_req = _request(cfg, rng, 0, plen=11)
    ref = _monolithic(m, params, ref_req)
    chunked = ServeEngine(m, params, max_batch=2, max_seq=32,
                          prefill_chunk_tokens=4)
    req = _clone(ref_req, 1)
    chunked.submit(req)
    chunked.run_until_drained(max_steps=200)
    assert list(req.out_tokens) == ref, (req.out_tokens, ref)


# ---------------------------------------------------------------------------
# disaggregated golden tests: prefill on A, ship, decode on B
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_disagg_token_identity(arch):
    """Prefill on a prefill-specialized replica, RSES-wire handoff, decode
    on a decode-specialized replica == the monolithic greedy stream, on
    every family.  Dense uses the chunked-prefill admission path; families
    without a chunkable prefill take the fused whole-prompt path — the
    handoff contract is identical."""
    cfg, m, params = _setup(arch)
    rng = np.random.default_rng(0)
    ref_req = _request(cfg, rng, 0)
    ref = _monolithic(m, params, ref_req)

    pre = ServeEngine(m, params, max_batch=2, max_seq=32, role="prefill",
                      prefill_chunk_tokens=4)
    dec = ServeEngine(m, params, max_batch=2, max_seq=32, role="decode")
    gw = FleetGateway([pre, dec])
    req = _clone(ref_req, 1)
    d = gw.submit(req)
    assert d.replica == 0            # only prefill-capable replica
    gw.run_until_drained(max_steps=500)
    assert req.done
    assert list(req.out_tokens) == ref, (arch, req.out_tokens, ref)
    s = gw.stats()
    assert s["prefill_handoffs"] == 1
    assert s["roles"] == ["prefill", "decode"]
    assert pre.active_count() == 0   # prefill replica never took a slot
    bd = gw.ttft_breakdown()[1]
    assert bd["source"] == 0 and bd["dest"] == 1
    assert bd["prefill_s"] is not None and bd["ship_s"] > 0.0
    assert bd["first_decode_s"] is not None
    assert bd["nbytes"] > 0


def test_disagg_mid_prefill_chunk_export_token_identity():
    """A session exported *mid-prefill-chunk* (export_prefill), shipped
    over the wire with its v3 ``prefilled`` marker, resumes chunked
    prefill on another engine and still emits the monolithic stream."""
    cfg, m, params = _setup("smollm-135m")
    rng = np.random.default_rng(1)
    ref_req = _request(cfg, rng, 0, plen=11)
    ref = _monolithic(m, params, ref_req)

    a = ServeEngine(m, params, max_batch=2, max_seq=32,
                    prefill_chunk_tokens=4)
    req = _clone(ref_req, 1)
    a.submit(req)
    a.step()                         # chunk 1: 4 of 11 prompt tokens
    a.step()                         # chunk 2: 8 of 11
    sess = a.export_prefill(req.rid)
    assert sess.prefilled == 8
    shipped = decode_session(encode_session(sess))
    assert shipped.prefilled == 8
    shipped.req = req                # in-process identity (fleet-tier rule)
    b = ServeEngine(m, params, max_batch=2, max_seq=32,
                    prefill_chunk_tokens=4)
    b.import_session(shipped)
    b.run_until_drained(max_steps=200)
    assert req.done
    assert list(req.out_tokens) == ref, (req.out_tokens, ref)


# ---------------------------------------------------------------------------
# satellite: prefill chunks are their own latency signal
# ---------------------------------------------------------------------------

def test_prefill_chunks_never_feed_interference_detector():
    """Unit: a storm of slow prefill-chunk samples must not quarantine a
    replica — record_prefill_chunk is a separate signal from record_step
    (a long prompt's chunks are legitimately slower than decode steps)."""
    r = FleetRouter(2)
    for _ in range(50):
        r.record_step(0, 0.010)      # healthy decode baseline
    for _ in range(50):
        r.record_prefill_chunk(0, 5.0)   # 500x "spike" — but it's prefill
    assert 0 not in r.detector.quarantined
    assert r.stats()["prefill_chunk_ema"][0] > 0.0
    # the same magnitude through the decode-step signal DOES trip it
    for _ in range(50):
        r.record_step(1, 0.010)
    for _ in range(50):
        r.record_step(1, 5.0)
    assert 1 in r.detector.quarantined


def test_long_prompt_admitted_mid_decode_keeps_replica_healthy():
    """Regression (the detector-pollution bug): a long prompt chunk-admitted
    while another request decodes must not poison the decode-step signal —
    its chunks land on the prefill signal, decode steps stay homogeneous,
    nothing quarantines, and both streams match the monolithic runs."""
    cfg, m, params = _setup("smollm-135m")
    rng = np.random.default_rng(2)
    short_ref = _request(cfg, rng, 10, plen=4, max_new=8)
    long_ref = _request(cfg, rng, 11, plen=16, max_new=4)
    ref_s = _monolithic(m, params, short_ref)
    ref_l = _monolithic(m, params, long_ref)

    e = ServeEngine(m, params, max_batch=2, max_seq=32,
                    prefill_chunk_tokens=4)
    gw = FleetGateway([e])
    short = _clone(short_ref, 0)
    gw.submit(short)
    for _ in range(3):
        gw.pump()                    # short is mid-decode
    assert short.out_tokens and not short.done
    long = _clone(long_ref, 1)
    gw.submit(long)                  # 16 tokens: 4 chunks interleaved
    gw.run_until_drained(max_steps=200)
    assert list(short.out_tokens) == ref_s
    assert list(long.out_tokens) == ref_l
    s = gw.stats()
    assert s["quarantined"] == []
    assert s["prefill_chunk_ema"].get(0, 0.0) > 0.0   # chunks were seen —
    #                                       on the prefill signal, not steps


# ---------------------------------------------------------------------------
# satellite: role restrictions at the router
# ---------------------------------------------------------------------------

def test_route_allowed_restricts_and_degrades_within_subset():
    r = FleetRouter(3)
    for i in range(3):
        r.record_step(i, 0.01)
    # restriction honored
    for _ in range(10):
        d = r.route(64, 8, backlog=[0, 0, 0], allowed=[0, 1])
        assert d.replica in (0, 1)
    # all allowed replicas quarantined: degrade WITHIN the subset, never
    # escape to a disallowed (role-incapable) replica
    for _ in range(50):
        r.record_step(0, 5.0)
    assert 0 in r.detector.quarantined
    d = r.route(64, 8, backlog=[0, 0, 0], allowed=[0])
    assert d.replica in (0, None)
    with pytest.raises(ValueError):
        FleetRouter(2).route(64, 8, allowed=[])


def test_fleet_requires_both_roles_and_restricts_drains():
    cfg, m, params = _setup("smollm-135m")
    with pytest.raises(ValueError):
        FleetGateway([ServeEngine(m, params, max_batch=1, max_seq=32,
                                  role="prefill")])
    pre = ServeEngine(m, params, max_batch=1, max_seq=32, role="prefill")
    dec = ServeEngine(m, params, max_batch=1, max_seq=32, role="decode")
    gw = FleetGateway([pre, dec])
    assert gw.prefill_capable() == [0]
    assert gw.decode_capable() == [1]
    # region-tier feasibility: a fleet whose decode capacity can't hold a
    # session says so even if a prefill replica's cache could — drains
    # must never ship decode sessions toward prefill-only capacity
    assert gw.can_hold(4, 8)
    big = ServeEngine(m, params, max_batch=1, max_seq=64, role="prefill")
    gw2 = FleetGateway([big, ServeEngine(m, params, max_batch=1, max_seq=16,
                                         role="decode")])
    assert not gw2.can_hold(40, 8)   # only the prefill replica could


# ---------------------------------------------------------------------------
# satellite: RTT row aging in the region TraceTable
# ---------------------------------------------------------------------------

def test_rtt_rows_age_toward_trained_prior():
    """After a route flap nothing retrains a stale link row (the stale row
    itself steers traffic away — self-sealing), so rows decay on wall
    time toward the trained-link prior, anchored at the last delivery."""
    rr = RegionRouter(3, rtt_halflife_s=10.0)
    rr.record_rtt(0, 1, 0.100, now=0.0)
    rr.record_rtt(0, 2, 0.020, now=0.0)
    rr.record_rtt(1, 2, 0.020, now=0.0)
    # fresh rows (within one halflife) are untouched
    assert rr.age_links(5.0) == 0
    assert rr.links.value((0, 1), "rtt") == pytest.approx(0.100)
    # two halflives stale: the outlier decays 3/4 of the way to the prior
    assert rr.age_links(20.0) == 3
    prior = (0.100 + 0.020 + 0.020) / 3
    assert rr.links.value((0, 1), "rtt") == pytest.approx(
        prior + (0.100 - prior) * 0.25)
    # idempotent at the same `now` (anchor-based, not compounding)
    v = rr.links.value((0, 1), "rtt")
    rr.age_links(20.0)
    assert rr.links.value((0, 1), "rtt") == pytest.approx(v)
    # a real delivery re-anchors: the row is fresh again
    rr.record_rtt(0, 1, 0.030, now=21.0)
    aged = rr.age_links(25.0)
    assert aged == 2                 # only the two untouched links
    assert rr.stats()["rtt_decays"] == 8     # 3 + 3 (idempotent pass) + 2
    # disabled by default: halflife 0 never ages
    rr0 = RegionRouter(2)
    rr0.record_rtt(0, 1, 0.1, now=0.0)
    assert rr0.age_links(1e9) == 0
    assert rr0.links.value((0, 1), "rtt") == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# satellite: sampled tracing across the handoff
# ---------------------------------------------------------------------------

def test_sampled_tracer_unit():
    tr = SpanTracer("t", sample_rate=4)
    assert tr.trace_for(0) == "t/r0"
    assert tr.trace_for(1) is None and tr.trace_for(1) is None  # sticky
    tr.instant("x", tr.trace_for(1), "trk")     # sampled out: dropped
    tr.complete("y", tr.trace_for(1), "trk", ts=0.0, dur=1.0)
    with tr.span("z", tr.trace_for(1), "trk"):
        pass
    assert len(tr.events) == 0
    tr.instant("kept", tr.trace_for(4), "trk")
    assert len(tr.events) == 1
    # adopt force-binds over a local sampled-out verdict: a migrated-in
    # session that the origin sampled IN keeps its full timeline
    tr.adopt(1, "origin/r1")
    tr.instant("tail", tr.trace_for(1), "trk")
    assert [e["trace"] for e in tr.events][-1] == "origin/r1"
    # rate=1 keeps the legacy tracer-level timeline for trace=None
    tr1 = SpanTracer("u")
    tr1.instant("agg")
    assert tr1.events[0]["trace"] == "u"
    with pytest.raises(ValueError):
        SpanTracer(sample_rate=0)


def test_sampled_trace_propagates_across_disagg_handoff():
    """With sample_rate=2, a sampled-IN request's single timeline spans
    prefill replica -> ship -> decode replica; a sampled-OUT rid records
    nothing anywhere in the fleet."""
    cfg, m, params = _setup("smollm-135m")
    rng = np.random.default_rng(3)
    pre = ServeEngine(m, params, max_batch=2, max_seq=32, role="prefill",
                      prefill_chunk_tokens=4)
    dec = ServeEngine(m, params, max_batch=2, max_seq=32, role="decode")
    gw = FleetGateway([pre, dec])
    tr = SpanTracer("f", sample_rate=2)
    gw.attach_obs(tr, MetricRegistry())
    reqs = [_request(cfg, rng, rid, plen=9, max_new=4) for rid in (0, 1)]
    for r in reqs:
        gw.submit(r)
    gw.run_until_drained(max_steps=500)
    assert all(r.done for r in reqs)
    tid = tr.trace_for(0)            # rid 0: sampled in
    names = [e["name"] for e in tr.timeline(tid)]
    assert "prefill-handoff" in names and "disagg-ship" in names, names
    assert "decode-chunk" in names   # the decode side continued the trace
    tracks = tr.tracks(tid)
    assert any(t.endswith("/r0") for t in tracks)    # prefill replica
    assert any(t.endswith("/r1") for t in tracks)    # decode replica
    # rid 1: sampled out — no per-request events anywhere
    assert tr.trace_for(1) is None
    assert not [e for e in tr.events if e["trace"] == "f/r1"]


# ---------------------------------------------------------------------------
# wire v3
# ---------------------------------------------------------------------------

def test_wire_v3_prefilled_roundtrip_and_compat():
    req = Request(rid=7, prompt=np.arange(5, dtype=np.int32), max_new=4)
    from repro.serve.engine import Session
    part = Session(req=req, pos=3, cur_token=0,
                   cache={"k": np.ones((2, 3, 4), np.float32)}, prefilled=3)
    data = encode_session(part)
    assert wire_header(data)["version"] == WIRE_VERSION >= 3
    got = decode_session(data)
    assert got.prefilled == 3
    # complete sessions omit the key and decode with prefilled=None
    full = Session(req=req, pos=3, cur_token=9,
                   cache={"k": np.ones((2, 3, 4), np.float32)})
    assert decode_session(encode_session(full)).prefilled is None
    # a v2 header over the same body is refused: pre-v6 payloads carry KV
    # in older layouts, and a v6 reader must not guess at their layout
    import struct
    hdr = struct.Struct(">4sBBI")
    magic, ver, codec, crc = hdr.unpack_from(data)
    v2 = hdr.pack(magic, 2, codec, crc) + data[hdr.size:]
    with pytest.raises(WireFormatError, match="version 2"):
        wire_header(v2)
