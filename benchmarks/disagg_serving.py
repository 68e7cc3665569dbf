"""Beyond-paper: prefill-decode disaggregation at equal replica count.

A monolithic continuous-batching replica interleaves prompt prefill with
the decode steps of every session it carries: a long prompt admitted
mid-decode advances one chunk per engine step, so its time-to-first-token
multiplies by (1 + active decode sessions) — and the decode sessions pay
the prefill chunks right back as inflated TPOT.  Under a long-prompt-heavy
mix that head-of-line interference dominates the TTFT tail.

Disaggregation splits the same N replicas into prefill-specialized and
decode-specialized roles: prefills run back-to-back chunks on dedicated
replicas (no decode batch to interleave with), then the live KV session
ships over the RSES wire format to the decode-best replica — TTFT pays a
ship instead of the interference, and the tail collapses.

Two parts:

* :func:`simulate` — event-driven sim of both topologies at EQUAL replica
  count, driven by the real :class:`~repro.router.FleetRouter` (the
  disaggregated topology routes through the same ``allowed=`` role
  restriction the gateway uses).  Acceptance (CI): disaggregated beats
  monolithic by >= 1.25x on sim p99 TTFT, with p50 TPOT no worse than
  0.95x.
* :func:`engine_demo` — REAL engines: a prefill-role replica hands
  freshly prefilled sessions through the wire to decode-role replicas;
  token streams asserted identical to monolithic decode, and the chunked
  Pallas prefill kernel asserted against its jnp oracle in interpret
  mode.

:func:`main` writes ``BENCH_disagg.json`` (``BENCH_DISAGG_OUT``) for the
CI artifact trail.
"""

from __future__ import annotations

import json
import os

import numpy as np

from repro.router.router import FleetRouter

from . import common
from .common import row

N_REPLICAS = 4                  # equal total in both topologies
N_PREFILL = 2                   # disaggregated split: 2 prefill + 2 decode
BASE_TPOT = 0.02                # s/token, uncontended decode step
PREFILL_PER_TOKEN = 1.0e-4      # s/prompt token, uncontended prefill
SHIP_FIXED = 0.010              # s, handoff dispatch + adopt
SHIP_PER_TOKEN = 2.0e-5         # s/prompt token of KV on the wire
DECODE_CONCURRENCY = 0.02       # mild per-session batching overhead
MAX_INTERLEAVE = 6              # decode sessions a prefill interleaves with
                                # (engine batch bound — keeps the sim stable)


def gen_requests(n: int, seed: int, arrival_scale: float):
    """Long-prompt-heavy mix: ~60% of requests carry 2k/4k prompts (the
    interference drivers), the rest are short interactive turns; all
    decode long enough to be on-replica when the next prompt lands."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(arrival_scale, n))
    out = []
    for t in arrivals:
        if rng.random() < 0.6:
            plen = int(rng.choice([2048, 4096]))
        else:
            plen = int(rng.choice([256, 512]))
        out.append((float(t), plen, int(rng.choice([96, 128]))))
    return out


def _overlap(intervals, lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def simulate(disagg: bool, n_requests: int = 600, seed: int = 0,
             arrival_scale: float = 0.55) -> dict:
    """Event-driven sim.  Each replica has a serial prefill pipeline and a
    set of decode sessions.  Monolithic: every replica does both — a
    prefill's service time scales by (1 + active decodes) and each decode
    session's TPOT inflates by the share of its window the replica spent
    prefilling.  Disaggregated: prefill replicas run clean prefills, the
    session pays a wire ship, decode replicas never see a prompt chunk.
    Routing is the real FleetRouter either way (role restriction via
    ``allowed=``, exactly like the gateway)."""
    router = FleetRouter(N_REPLICAS)
    prefill_set = list(range(N_PREFILL)) if disagg else None
    decode_set = (list(range(N_PREFILL, N_REPLICAS)) if disagg
                  else list(range(N_REPLICAS)))
    prefill_free = np.zeros(N_REPLICAS)
    prefill_busy: list[list[tuple[float, float]]] = [
        [] for _ in range(N_REPLICAS)]
    decode_windows: list[list[tuple[float, float]]] = [
        [] for _ in range(N_REPLICAS)]
    ttfts, tpots = [], []
    for t_arr, plen, max_new in gen_requests(n_requests, seed,
                                             arrival_scale):
        for r in range(N_REPLICAS):     # retire finished work
            decode_windows[r] = [(a, b) for a, b in decode_windows[r]
                                 if b > t_arr]
            prefill_busy[r] = [(a, b) for a, b in prefill_busy[r]
                               if b > t_arr]
        backlog = [int(prefill_free[r] > t_arr) + len(decode_windows[r])
                   for r in range(N_REPLICAS)]
        d = router.route(plen, max_new, backlog=backlog,
                         allowed=prefill_set)
        pr = d.replica if d.replica is not None else (
            prefill_set or decode_set)[0]
        # --- prefill ---
        n_dec = min(len(decode_windows[pr]), MAX_INTERLEAVE)
        s_p = plen * PREFILL_PER_TOKEN * (1 + (0 if disagg else n_dec))
        start = max(t_arr, float(prefill_free[pr]))
        prefill_free[pr] = start + s_p
        prefill_busy[pr].append((start, start + s_p))
        ship = SHIP_FIXED + plen * SHIP_PER_TOKEN if disagg else 0.0
        ttft = start + s_p + ship - t_arr
        ttfts.append(ttft)
        # --- decode placement ---
        cands = decode_set
        dr = min(cands, key=lambda r: len(decode_windows[r]))
        d0 = start + s_p + ship
        base = BASE_TPOT * (1 + DECODE_CONCURRENCY * len(decode_windows[dr]))
        dur0 = max_new * base
        # monolithic: prompt chunks of OTHER requests interleave with this
        # session's decode steps — its TPOT inflates by the prefill share
        # of its window (disaggregated decode replicas never prefill)
        pf = (_overlap(prefill_busy[dr], d0, d0 + dur0) / dur0
              if not disagg and dur0 > 0 else 0.0)
        tpot = base * (1 + pf)
        decode_windows[dr].append((d0, d0 + max_new * tpot))
        tpots.append(tpot)
        # train the tables exactly like the gateway: service span only
        router.record_ttft(pr, int(d.req_class), s_p + ship,
                           prompt_len=plen)
        router.record_service(pr, s_p + ship, req_class=int(d.req_class))
        router.record_step(dr, tpot)
        if disagg:
            router.record_prefill_chunk(pr, s_p)
    out = common.latency_summary(ttfts)
    out["tpot_p50"] = float(np.percentile(tpots, 50))
    out["tpot_p99"] = float(np.percentile(tpots, 99))
    return out


def engine_demo(quick: bool = False) -> dict:
    """Real engines: chunked prefill on a prefill-role replica, RSES-wire
    handoff, decode on decode-role replicas — token streams asserted
    identical to monolithic decode; the chunked Pallas prefill kernel
    asserted against its jnp oracle in interpret mode."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.ragged_prefill import (force_pallas,
                                              ragged_prefill_attention)
    from repro.kernels.ragged_prefill.ref import ragged_prefill_ref
    from repro.models import get_model
    from repro.router import FleetGateway
    from repro.serve import Request, ServeEngine

    # kernel identity: Pallas (interpret) vs the dense jnp reference
    rng = np.random.default_rng(0)
    B, Smax, T, Hq, Hkv, hd = 3, 32, 8, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Smax, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Smax, hd)), jnp.float32)
    start = jnp.asarray([0, 5, 11], jnp.int32)
    qlen = jnp.asarray([T, T - 3, T], jnp.int32)
    ref = ragged_prefill_ref(q, k, v, start, qlen)
    with force_pallas():
        got = ragged_prefill_attention(q, k, v, start, qlen, block_k=8)
    kernel_identity = bool(np.allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5))
    assert kernel_identity, "chunked prefill kernel diverged from oracle"

    cfg = get_config("smollm-135m", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    n = 2 if quick else 4
    max_new = 8
    prompts = [rng.integers(0, cfg.vocab, int(p))
               for p in np.linspace(6, 14, n)]

    refs = []
    for p in prompts:                    # monolithic reference streams
        e = ServeEngine(m, params, max_batch=2, max_seq=48)
        r = Request(rid=900, prompt=p.copy(), max_new=max_new)
        e.submit(r)
        e.run_until_drained(200)
        refs.append(list(r.out_tokens))

    pre = ServeEngine(m, params, max_batch=4, max_seq=48, role="prefill",
                      prefill_chunk_tokens=4)
    decs = [ServeEngine(m, params, max_batch=2, max_seq=48, role="decode")
            for _ in range(2)]
    gw = FleetGateway([pre, *decs])
    reqs = [Request(rid=i, prompt=p.copy(), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        gw.submit(r)
    gw.run_until_drained(1000)
    identical = all(r.done and list(r.out_tokens) == refs[i]
                    for i, r in enumerate(reqs))
    st = gw.stats()
    assert identical, "disaggregated token streams diverged"
    assert st["prefill_handoffs"] == n, "not every session shipped"
    assert pre.active_count() == 0, "prefill replica took a decode slot"
    bd = gw.ttft_breakdown()
    return {"token_identical": identical, "kernel_identity": kernel_identity,
            "handoffs": st["prefill_handoffs"],
            "ship_bytes": int(sum(b["nbytes"] for b in bd.values())),
            "mean_ship_s": float(np.mean([b["ship_s"]
                                          for b in bd.values()]))}


def main(quick: bool = False) -> None:
    # the sim is sub-second: always run the full stream so the asserted
    # ratio has real tail samples (--quick only shrinks the engine demo)
    n = 600
    mono = simulate(disagg=False, n_requests=n)
    dis = simulate(disagg=True, n_requests=n)
    for name, m in (("monolithic", mono), ("disagg", dis)):
        row(f"disagg_serving_{name}", 1e6 * m["mean"],
            f"p50={m['p50']:.3f}s;p99={m['p99']:.3f}s;"
            f"tpot_p50={m['tpot_p50'] * 1e3:.1f}ms;n={m['n']}")
    ttft_ratio = mono["p99"] / dis["p99"]
    tpot_ratio = mono["tpot_p50"] / dis["tpot_p50"]
    row("disagg_serving_speedup", 1e6 * dis["mean"],
        f"p99_ttft_ratio={ttft_ratio:.2f}x;tpot_ratio={tpot_ratio:.2f}x")
    demo = engine_demo(quick=quick)
    row("disagg_serving_engines", 0.0,
        f"identical={demo['token_identical']};"
        f"kernel={demo['kernel_identity']};handoffs={demo['handoffs']};"
        f"ship_bytes={demo['ship_bytes']}")
    bench = {"n_requests": n,
             "replicas": N_REPLICAS, "prefill_replicas": N_PREFILL,
             "sim": {"monolithic": mono, "disagg": dis,
                     "p99_ttft_ratio": ttft_ratio,
                     "tpot_ratio": tpot_ratio},
             "engine": demo}
    out = os.environ.get("BENCH_DISAGG_OUT", "BENCH_disagg.json")
    with open(out, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    main()
