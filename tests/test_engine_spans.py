"""The program's own spans: the phases inside ``ServeEngine.step`` and
``FleetGateway.pump``, their args, their survival under sampling, and the
moment ``Request.t_admit`` marks under chunked admission."""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import get_model
from repro.obs import SpanTracer
from repro.router import FleetGateway
from repro.serve import Request, ServeEngine

PHASES = {"engine.step", "engine.admit", "engine.insert",
          "engine.prefill.dispatch", "engine.prefill.sync", "engine.upload",
          "engine.decode.dispatch", "engine.decode.sync", "engine.harvest"}


@pytest.fixture(scope="module")
def model():
    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _engine(model, **kw):
    cfg, m, params = model
    kw = {"max_batch": 4, "max_seq": 64, "decode_chunk": 2,
          "prefill_chunk_tokens": 8, **kw}
    return ServeEngine(m, params, **kw)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab, n).astype(
        np.int32)


def _inside(tracer, outer):
    """Phase spans on ``outer``'s track that lie inside it."""
    t0, t1 = outer["ts"], outer["ts"] + outer["dur"]
    return [e for e in tracer.events if e is not outer and e["ph"] == "X"
            and e["track"] == outer["track"]
            and e["name"].startswith(("engine.", "gateway."))
            and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1]


def test_a_step_holds_every_phase_with_the_engine_state_as_args(model):
    cfg = model[0]
    eng = _engine(model)
    tr = SpanTracer("t")
    eng.attach_obs(tracer=tr, name="e0")
    for i, n in enumerate((6, 5, 20)):
        eng.submit(Request(rid=i, prompt=_prompt(cfg, n, i), max_new=8))
    eng.step()          # prefills rid 0 whole; nothing decodes yet
    state = {"active": eng.active_count(), "capacity": eng.max_batch,
             "queued": len(eng.queue),
             "prefilling": len(eng.prefilling) + len(eng._prefill_ready),
             "backlog_tokens": 5 + 20, "device": eng.device.id}
    tr.events.clear()
    eng.step()          # slots rid 0, prefills rid 1 whole, decodes
    steps = [e for e in tr.events if e["name"] == "engine.step"]
    assert len(steps) == 1
    step = steps[0]
    assert step["args"] == state and step["track"] == "e0"
    assert step["trace"] == "t"
    kids = _inside(tr, step)
    assert {e["name"] for e in kids} | {"engine.step"} == PHASES
    assert {e["name"] for e in tr.events
            if e["name"].startswith("engine.")} == PHASES
    syncs = [e for e in kids if e["name"].endswith(".sync")]
    assert {e["name"] for e in syncs} == {"engine.prefill.sync",
                                          "engine.decode.sync"}
    admit = next(e for e in kids if e["name"] == "engine.admit")
    assert admit["args"] == {"admitted": 1}
    insert = next(e for e in kids if e["name"] == "engine.insert")
    assert insert["args"]["slot"] == 0
    # the per-request spans keep their names and args
    chunk = next(e for e in tr.events if e["name"] == "prefill-chunk")
    assert chunk["trace"] == "t/r1"
    assert chunk["args"] == {"tokens": 5, "consumed": 5}


def test_engine_spans_survive_sampling(model):
    cfg = model[0]
    eng = _engine(model)
    tr = SpanTracer("t", sample_rate=4)
    eng.attach_obs(tracer=tr, name="e0")
    for rid in (1, 2, 3):                  # every request sampled out
        eng.submit(Request(rid=rid, prompt=_prompt(cfg, 6, rid), max_new=4))
    eng.run_until_drained(max_steps=50)
    names = {e["name"] for e in tr.events}
    assert PHASES <= names
    assert not names & {"prefill-chunk", "decode-chunk", "finish"}
    assert {e["trace"] for e in tr.events} == {"t"}


def test_t_admit_marks_the_first_chunk_not_the_queue(model):
    cfg = model[0]
    eng = _engine(model)
    tr = SpanTracer("t")
    eng.attach_obs(tracer=tr)
    a = Request(rid=0, prompt=_prompt(cfg, 30, 0), max_new=3)
    b = Request(rid=1, prompt=_prompt(cfg, 30, 1), max_new=3)
    eng.submit(a)
    eng.submit(b)
    eng.run_until_drained(max_steps=100)
    assert a.done and b.done
    chunks = [e for e in tr.events if e["name"] == "prefill-chunk"]
    a_chunks = [e for e in chunks if e["trace"] == "t/r0"]
    b_chunks = [e for e in chunks if e["trace"] == "t/r1"]
    assert len(a_chunks) == len(b_chunks) == 4      # 30 tokens, chunks of 8
    a_end = a_chunks[-1]["ts"] + a_chunks[-1]["dur"]
    assert b.t_admit >= a_end
    assert b.t_admit == b_chunks[0]["ts"]
    assert a.t_admit == a_chunks[0]["ts"]
    assert a.t_admit < a.t_first <= b.t_admit < b.t_first


def test_fleet_pump_spans_hold_each_engine_step(model):
    cfg = model[0]
    gw = FleetGateway([_engine(model), _engine(model)])
    tr = SpanTracer("t")
    gw.attach_obs(tracer=tr, name="fleet")
    for rid in range(4):
        gw.submit(Request(rid=rid, prompt=_prompt(cfg, 6, rid), max_new=4))
    tr.events.clear()
    gw.pump()
    pumps = [e for e in tr.events if e["name"] == "gateway.pump"]
    assert len(pumps) == 1
    pump = pumps[0]
    assert pump["track"] == "fleet" and pump["args"] == {"tick": 1}
    kids = _inside(tr, pump)
    assert [e["name"] for e in kids] == ["gateway.control",
                                         "gateway.harvest"]
    control, harvest = kids
    t0, t1 = pump["ts"], pump["ts"] + pump["dur"]
    steps = [e for e in tr.events if e["name"] == "engine.step"]
    assert sorted(e["track"] for e in steps) == ["fleet/r0", "fleet/r1"]
    for s in steps:
        assert t0 <= s["ts"] and s["ts"] + s["dur"] <= t1
        assert control["ts"] + control["dur"] <= s["ts"]
        assert s["ts"] + s["dur"] <= harvest["ts"]
