"""Region tier: session wire format, WAN-aware routing, and cross-region
failover.

The acceptance bar for the fourth PTT scale: a browned-out fleet's live
sessions drain to the WAN-cost-best healthy fleet *through the versioned
byte wire format* (never an in-process object handoff) with greedy token
streams identical to uninterrupted decode — and a session whose WAN move
doesn't pay (MigrationCost + WanCost ranked search puts the source first)
is never even exported."""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.tracetable import (Candidate, MigrationCost, QueueAware,
                                   SearchContext, TraceTable, WanCost)
from repro.models import get_model
from repro.region import (LoopbackTransport, RegionGateway, RegionRouter,
                          WIRE_COMPAT, WIRE_VERSION, WireFormatError,
                          decode_session, encode_session, wire_header)
from repro.router import FleetGateway
from repro.serve import Request, ServeEngine, Session


def _synthetic_session() -> Session:
    rng = np.random.default_rng(0)
    req = Request(rid=7, prompt=np.arange(5, dtype=np.int64), max_new=9,
                  tenant="acme",
                  extras={"image_embeds": rng.normal(
                      size=(2, 3)).astype(np.float32)},
                  out_tokens=[1, 2, 3], t_first=1.5, t_admit=1.25)
    return Session(req=req, pos=8, cur_token=3,
                   cache={"k": rng.normal(size=(1, 2, 8, 4)).astype(
                       np.float32),
                          "state": rng.normal(size=(1, 4)).astype(
                       np.float64)})


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def test_wire_round_trip_preserves_session():
    sess = _synthetic_session()
    out = decode_session(encode_session(sess))
    assert out.req is not sess.req           # a NEW object crossed: bytes,
    assert out.pos == sess.pos               # not an in-process handoff
    assert out.cur_token == sess.cur_token
    assert out.req.rid == sess.req.rid
    assert out.req.max_new == sess.req.max_new
    assert out.req.tenant == sess.req.tenant
    assert out.req.out_tokens == sess.req.out_tokens
    assert out.req.t_first == sess.req.t_first
    assert np.array_equal(out.req.prompt, sess.req.prompt)
    for k in sess.cache:
        assert np.array_equal(out.cache[k], sess.cache[k])
        assert out.cache[k].dtype == sess.cache[k].dtype
    for k in sess.req.extras:
        assert np.array_equal(out.req.extras[k], sess.req.extras[k])


def test_wire_header_records_codec_and_version():
    from repro.checkpoint import default_codec
    data = encode_session(_synthetic_session())
    h = wire_header(data)
    assert h["version"] == WIRE_VERSION
    # the checkpoint codec path is reused: zstd when importable, zlib
    # fallback otherwise — whichever this build wrote is in the header
    assert h["codec"] == default_codec()
    assert h["nbytes"] == len(data)
    # explicit zlib always encodes and round-trips on any build
    z = encode_session(_synthetic_session(), codec="zlib")
    assert wire_header(z)["codec"] == "zlib"
    assert decode_session(z).pos == 8


def test_wire_rejects_corrupt_and_foreign_payloads():
    data = encode_session(_synthetic_session())
    # flipped payload byte: checksum catches it before any deserialization
    bad = bytearray(data)
    bad[-1] ^= 0xFF
    with pytest.raises(WireFormatError, match="checksum"):
        decode_session(bytes(bad))
    # truncation
    with pytest.raises(WireFormatError, match="checksum"):
        decode_session(data[:-3])
    with pytest.raises(WireFormatError, match="too short"):
        decode_session(data[:4])
    # foreign bytes
    with pytest.raises(WireFormatError, match="magic"):
        decode_session(b"XXXX" + data[4:])
    # any version outside the compat set must refuse, not misparse — the
    # CRC covers only the body, so both a future version and a corrupted
    # version byte (2 -> 0) land here
    for v in (WIRE_VERSION + 1, 0):
        assert v not in WIRE_COMPAT
        fut = bytearray(data)
        fut[4] = v
        with pytest.raises(WireFormatError, match="version"):
            decode_session(bytes(fut))
    # unknown codec id
    unk = bytearray(data)
    unk[5] = 99
    with pytest.raises(WireFormatError, match="codec"):
        decode_session(bytes(unk))
    with pytest.raises(WireFormatError):
        encode_session(_synthetic_session(), codec="lz4")


def test_wire_v1_payload_still_decodes():
    """v1-v5 payloads carry KV in older layouts; v6 carries the
    sequence-minor cache layout, so an older payload no longer decodes: it
    is refused at the header, never inserted as transposed KV.  Optional
    keys stay optional: a v6 payload without "trace"/"prefilled"/"delivery"
    decodes with None."""
    assert WIRE_VERSION == 6 and WIRE_COMPAT == frozenset({6})
    sess = _synthetic_session()
    assert sess.trace is None
    data = encode_session(sess)
    out = decode_session(data)
    assert out.trace is None and out.prefilled is None
    assert out.delivery is None
    for old in (1, 2, 3, 4, 5):
        buf = bytearray(data)
        buf[4] = old
        with pytest.raises(WireFormatError, match="version"):
            decode_session(bytes(buf))


@pytest.mark.parametrize("version", [1, 4, 5])
def test_wire_pre_v5_session_refused_by_engine(version):
    """A live session shipped by a writer older than v6 (KV in an older
    layout) is refused by the importing engine, which keeps nothing of
    it."""
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab, 3)
    a = ServeEngine(m, params, max_batch=2, max_seq=48)
    b = ServeEngine(m, params, max_batch=2, max_seq=48)
    a.submit(Request(rid=1, prompt=prompt, max_new=8))
    a.step()
    old = bytearray(a.export_session_wire(1))
    old[4] = version                 # the CRC covers only the body
    with pytest.raises(WireFormatError, match="version"):
        b.import_session_wire(bytes(old))
    assert not b.sessions_in and b.active_count() == 0


def test_wire_carries_trace_context():
    """v2's optional trace field: present -> round-trips verbatim; the
    migrated request's causal identity survives the byte boundary."""
    sess = _synthetic_session()
    sess.trace = {"trace_id": "fleetA/r7"}
    out = decode_session(encode_session(sess))
    assert out.trace == {"trace_id": "fleetA/r7"}
    assert wire_header(encode_session(sess))["version"] == WIRE_VERSION


def test_engine_wire_round_trip_token_identity():
    """export_session_wire -> bytes -> import_session_wire resumes the
    exact greedy stream (the serve-engine surface of the wire format)."""
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 6)

    ref = Request(rid=0, prompt=prompt.copy(), max_new=10)
    e = ServeEngine(m, params, max_batch=2, max_seq=48)
    e.submit(ref)
    e.run_until_drained(200)

    mig = Request(rid=1, prompt=prompt.copy(), max_new=10)
    a = ServeEngine(m, params, max_batch=2, max_seq=48)
    b = ServeEngine(m, params, max_batch=2, max_seq=48)
    a.submit(mig)
    for _ in range(3):
        a.step()
    data = a.export_session_wire(mig.rid)
    assert wire_header(data)["nbytes"] == len(data)
    b.import_session_wire(data)
    handle = b.sessions_in[0].req            # the decoded copy that will
    assert handle is not mig                 # finish the generation
    assert handle.rid == mig.rid
    b.run_until_drained(200)
    assert handle.done
    assert not mig.done                      # original froze at export
    assert handle.out_tokens[:10] == ref.out_tokens[:10], (
        handle.out_tokens, ref.out_tokens)


# ---------------------------------------------------------------------------
# WanCost
# ---------------------------------------------------------------------------

def test_wan_cost_charges_hops_and_learns_links():
    links = TraceTable((3, 3), metrics=("rtt",))
    wan = WanCost(links, egress_per_byte=1e-9, bytes_per_token=1000.0)
    cand = lambda f: Candidate(key=(0, f), item=f)
    ctx = SearchContext(tokens=2048, origin=0)
    # staying home is free; untrained link charges egress only
    assert wan.cost(0.0, cand(0), ctx) == 0.0
    assert wan.cost(0.0, cand(1), ctx) == pytest.approx(
        1e-9 * 1000.0 * 2048)
    # the link row is the paper's EMA: first sample adopted, then 4:1
    links.update((0, 1), 0.1)
    assert wan.rtt(0, 1) == pytest.approx(0.1)
    links.update((0, 1), 0.2)
    assert wan.rtt(0, 1) == pytest.approx((4 * 0.1 + 0.2) / 5)
    assert wan.cost(0.0, cand(1), ctx) == pytest.approx(
        wan.rtt(0, 1) + 1e-9 * 1000.0 * 2048)
    # origin falls back to ctx.current (sticky composition) and the model
    # composes additively with QueueAware + MigrationCost
    ctx2 = SearchContext(tokens=100, current=0)
    composed = QueueAware(value_per_token=False) + wan + MigrationCost(
        fixed=0.5)
    assert composed.cost(0.0, cand(1), ctx2) == pytest.approx(
        wan.rtt(0, 1) + 1e-9 * 1000.0 * 100 + 0.5)
    assert composed.cost(0.0, cand(0), ctx2) == 0.0


def test_region_sticky_affinity_weighs_wan_cost():
    """A chatty decode stays on its home fleet when the WAN hop outweighs
    the TPOT win, and leaves when the link is cheap and the win decisive."""
    expensive = RegionRouter(2)
    cheap = RegionRouter(2)
    for rr, rtt in ((expensive, 1.0), (cheap, 0.001)):
        for _ in range(6):
            rr.record_tpot(0, 0.1)      # home: slow decode
            rr.record_tpot(1, 0.01)     # away: 10x faster
            rr.record_rtt(0, 1, rtt)
    d = expensive.route(16, 256, origin=0, affinity=0)
    assert d.fleet == 0 and not d.wan_hop
    d = cheap.route(16, 256, origin=0, affinity=0)
    assert d.fleet == 1 and d.wan_hop


def test_region_route_reports_hop_from_the_charged_home():
    """When the affinity fleet is browned out the search runs globally
    from the ingress region — and the decision reports hops against that
    same home, not the dead affinity (no phantom wan_hop/predicted RTT)."""
    rr = RegionRouter(2)
    rr.record_rtt(1, 0, 0.2)
    rr.brownout(0)
    d = rr.route(16, 256, origin=1, affinity=0)
    assert d.fleet == 1
    assert not d.wan_hop                     # served at the ingress region
    assert d.predicted == pytest.approx(0.0)  # untrained rows, no RTT added


# ---------------------------------------------------------------------------
# region failover (real engines, wire transport)
# ---------------------------------------------------------------------------

def _build_region(arch: str, n_fleets: int = 2, engines_per_fleet: int = 1,
                  router: RegionRouter | None = None,
                  link_rtt=None):
    cfg = get_config(arch, reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    fleets = [FleetGateway([ServeEngine(m, params, max_batch=2, max_seq=48)
                            for _ in range(engines_per_fleet)])
              for _ in range(n_fleets)]
    tr = LoopbackTransport(link_rtt=link_rtt)
    return cfg, m, params, RegionGateway(
        fleets, router=router or RegionRouter(n_fleets), transport=tr)


@pytest.mark.parametrize("arch", ("smollm-135m", "granite-moe-1b-a400m"))
def test_region_failover_token_identity(arch):
    """Region-wide brownout drains every live session cross-region through
    the wire format with byte-identical greedy continuation — across
    attention-cache and MoE families."""
    cfg, m, params, rg = _build_region(arch, link_rtt=lambda s, d: 0.08)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 6) for _ in range(3)]
    max_new = 10

    refs = []
    for i, p in enumerate(prompts):
        e = ServeEngine(m, params, max_batch=2, max_seq=48)
        r = Request(rid=100 + i, prompt=p.copy(), max_new=max_new)
        e.submit(r)
        e.run_until_drained(200)
        refs.append(list(r.out_tokens))

    reqs = [Request(rid=i, prompt=p.copy(), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        d = rg.submit(r, origin=0, affinity=0)
        assert d.fleet == 0                  # sticky: everything starts home
    for _ in range(3):
        rg.pump()
    rg.brownout(0)
    rg.pump()
    # the browned-out fleet is EMPTY after one pump: all live sessions left
    assert sum(e.active_count() + e.pending()
               for e in rg.fleets[0].engines) == 0
    st = rg.stats()
    assert st["wan_ships"] >= 1 and st["wan_bytes"] > 0
    # learned link row trained from the drain's observed delivery time
    assert st["rtt_rows"][0][1] == pytest.approx(0.08)

    rg.run_until_drained(500)
    for i, ref in enumerate(refs):
        h = rg.request(i)
        assert h.done
        assert h.out_tokens[:max_new] == ref[:max_new], (
            arch, i, h.out_tokens, ref)
    # at least one live handle is a decoded copy — proof the drain went
    # through bytes, not an in-process object handoff
    assert any(rg.request(i) is not reqs[i] for i in range(len(reqs)))


@pytest.mark.parametrize("kind", ("wan", "migration"))
def test_region_stay_home_skips_export(kind):
    """When the ranked MigrationCost + WanCost search puts the browned-out
    source first, the session is never exported: no wire bytes move and
    the request finishes (slowly) where its cache already is."""
    if kind == "wan":
        router = RegionRouter(2, egress_per_byte=1.0, bytes_per_token=1e6)
    else:
        router = RegionRouter(2, migration=MigrationCost(fixed=1e9))
    cfg, m, params, rg = _build_region("smollm-135m", router=router)
    # train TPOT rows so the ranked search runs on evidence, not bootstrap
    for _ in range(4):
        rg.router.record_tpot(0, 0.01)
        rg.router.record_tpot(1, 0.01)
    rng = np.random.default_rng(0)
    req = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 6), max_new=10)
    rg.submit(req, origin=0, affinity=0)
    for _ in range(3):
        rg.pump()
    assert not req.done
    rg.brownout(0)
    rg.pump()
    st = rg.stats()
    assert st["stay_home_skips"] >= 1
    assert st["wan_ships"] == 0 and st["wan_bytes"] == 0
    rg.run_until_drained(500)
    assert req.done                          # finished on the browned-out
    assert rg.request(0) is req              # fleet: the original handle


def test_region_drain_reroutes_unstarted_requests():
    """Queued-but-unstarted requests on a browned-out fleet re-route to a
    healthy fleet as plain requests (no cache state -> no wire cost)."""
    cfg, m, params, rg = _build_region("smollm-135m")
    rng = np.random.default_rng(0)
    # more requests than fleet 0's slots so some stay queued
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 6), max_new=8)
            for i in range(5)]
    for r in reqs:
        rg.submit(r, origin=0, affinity=0)
    rg.pump()
    rg.brownout(0)
    rg.run_until_drained(500)
    assert all(rg.request(r.rid).done for r in reqs)
    assert rg.fleets[1].stats()["served"] >= 1
