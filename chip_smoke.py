#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, at published widths.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --fleet4   # four one-chip replicas behind the router

One chip (the default) builds smollm-135m at its published widths (30
layers, d_model 576, 9 query / 3 KV heads, vocab 49152, bf16 compute) with
random weights from ``--seed``, then, in one process:

1. lowers ``decode_fused`` and ``prefill_chunk`` and requires both Pallas
   kernels in them as ``tpu_custom_call``;
2. runs both kernels on real-width inputs against their jnp oracles (and
   the decode kernel's in-place cache write against the oracle's);
3. serves 8 requests (prompts of 64..1500 tokens, 32 new tokens each)
   through chunked prefill, and 4 requests of two prompt lengths through
   whole-prompt prefill, on a ``ServeEngine`` with ``max_batch=8``,
   ``max_seq=2048`` and ``decode_chunk=4``;
4. serves the chunked requests again and exports each live session
   halfway (the migration path); every K/V row in it must match a float32
   reference prefill over the same tokens within ``KV_RTOL``, which catches
   a row written at the wrong position or head;
5. teacher-forces every generated token through a float32
   ``Model.forward`` at ``highest`` matmul precision and requires the
   engine's greedy choice to be within ``LOGIT_MARGIN`` of the reference
   argmax.

``--fleet4`` runs only this: four ``ServeEngine`` replicas, replica i on
``jax.devices()[i]``, behind a ``FleetGateway``, serve a seeded mix of 32
requests; the same requests then run through one engine on chip 0 with the
same ``max_batch``/``max_seq``/``decode_chunk``.  Every replica must hold
its cache on its own chip and serve requests, and every token stream must
equal the one-engine run.

The script refuses to run without a TPU.  Times it prints are smoke timings
with compilation included, not benchmark numbers.  Any failed check exits
non-zero; on success the last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "smollm-135m"
MAX_BATCH, MAX_SEQ, DECODE_CHUNK, PREFILL_CHUNK = 8, 2048, 4, 128
PROMPT_MIN, PROMPT_MAX, MAX_NEW = 64, 1500, 32
REF_LEN = 1536            # teacher-forced forward length (>= 1500 + 32 - 1)
KERNEL_ATOL = 1e-2        # bf16 inputs, f32 accumulation: max |kernel - ref|
LOGIT_MARGIN = 0.1        # engine token's f32 logit may trail the argmax by this
KV_RTOL = 0.1             # exported session K/V row vs the f32 reference, relative


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, read from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"compile_s={self.seconds:.2f} cache_hits={self.hits} "
                f"cache_misses={self.misses}")


def make_requests(rng, vocab: int, lengths, max_new, rid0: int = 0):
    from repro.serve import Request
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(0, vocab, int(n)).astype(np.int32),
                    max_new=int(m))
            for i, (n, m) in enumerate(zip(lengths, max_new))]


def clone(reqs):
    return [dataclasses.replace(r, out_tokens=[], done=False, t_first=None,
                                t_admit=None) for r in reqs]


def serve(engine, reqs, max_steps: int = 100_000) -> None:
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained(max_steps=max_steps)
    for r in reqs:
        require(r.done and len(r.out_tokens) == r.max_new,
                f"request {r.rid} (prompt {len(r.prompt)}) served "
                f"{len(r.out_tokens)}/{r.max_new} tokens")


# -- one chip ------------------------------------------------------------------

def check_kernels_lowered(model, params) -> None:
    """Both Pallas kernels appear in the lowered serving steps."""
    import jax
    import jax.numpy as jnp
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    dec = model.decode_fused.lower(
        params, i32(MAX_BATCH, 1), i32(MAX_BATCH),
        model.cache_spec(MAX_BATCH, MAX_SEQ), DECODE_CHUNK).as_text()
    pre = model.prefill_chunk.lower(
        params, i32(1, PREFILL_CHUNK), model.cache_spec(1, MAX_SEQ),
        i32(1), i32(1)).as_text()
    require("tpu_custom_call" in dec, "decode_fused has no tpu_custom_call")
    require("tpu_custom_call" in pre, "prefill_chunk has no tpu_custom_call")
    print(f"kernels: tpu_custom_call in decode_fused={dec.count('tpu_custom_call')} "
          f"prefill_chunk={pre.count('tpu_custom_call')}")


def kernel_errors(cfg, seed: int) -> tuple[float, float]:
    """Max |kernel - oracle| of both ragged kernels on real-width bf16
    inputs (random q/k/v, ragged positions and chunk windows)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ragged_decode import ragged_decode_attention
    from repro.kernels.ragged_decode.ref import ragged_decode_ref
    from repro.kernels.ragged_prefill import ragged_prefill_attention
    from repro.kernels.ragged_prefill.ref import ragged_prefill_ref
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, hd, S = MAX_BATCH, cfg.n_heads, cfg.n_kv_heads, cfg.hd, MAX_SEQ
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    kc, vc = bf(2, B, Hkv, hd, S), bf(2, B, Hkv, hd, S)   # two stacked layers
    kn, vn = bf(B, Hkv, hd), bf(B, Hkv, hd)
    pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    q = bf(B, Hq, hd)
    got, gk, gv = jax.jit(ragged_decode_attention)(q, kc, vc, kn, vn, pos, 1)
    ref, rk, rv = jax.jit(ragged_decode_ref)(q, kc, vc, kn, vn, pos, 1)
    dec_err = float(jnp.max(jnp.abs(got - ref)))
    require(bool(jnp.all(gk == rk)) and bool(jnp.all(gv == rv)),
            "ragged_decode wrote the cache unlike the oracle")
    k, v = (c[1].swapaxes(-1, -2) for c in (rk, rv))   # (B, Hkv, S, hd)
    T = PREFILL_CHUNK
    q = bf(B, T, Hq, hd)
    start = jnp.asarray(rng.integers(0, S - T, B), jnp.int32)
    qlen = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)
    got = jax.jit(ragged_prefill_attention)(q, k, v, start, qlen)
    ref = jax.jit(ragged_prefill_ref)(q, k, v, start, qlen)
    pre_err = float(jnp.max(jnp.abs(got - ref)))
    return dec_err, pre_err


def teacher_forced_gaps(cfg, params, reqs, ref_len: int):
    """For every generated token: the float32 reference's argmax logit
    minus its logit for the engine's token (0 where they agree), and the
    reference's own top-1 minus top-2 logit (how hard the choice was)."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    ref_model = get_model(dataclasses.replace(cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        rows_of = jax.jit(lambda p, toks, rows: ref_model.forward(
            p, {"tokens": toks})[0, rows])
        gaps, margins = [], []
        for r in reqs:
            seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                       np.int32)])
            require(len(seq) <= ref_len, f"sequence {len(seq)} > {ref_len}")
            toks = np.zeros((1, ref_len), np.int32)
            toks[0, :len(seq)] = seq
            rows = len(r.prompt) - 1 + np.arange(len(r.out_tokens))
            logits = np.asarray(rows_of(params, jnp.asarray(toks),
                                        jnp.asarray(rows, np.int32)))
            chosen = logits[np.arange(len(rows)), r.out_tokens]
            gaps.append(logits.max(axis=1) - chosen)
            top2 = np.sort(logits, axis=1)[:, -2:]
            margins.append(top2[:, 1] - top2[:, 0])
    return np.concatenate(gaps), np.concatenate(margins)


def session_kv_errors(cfg, model, params, reqs, ref_len: int):
    """Serve ``reqs``, export each live session once it has generated half
    its tokens (the migration path), and compare its K/V cache slice
    with the float32 reference's K/V over the same tokens.  Returns the
    largest relative error of one (layer, position) K or V row and the
    number of positions compared.  Unlike the teacher-forced logits, this
    sees a KV row written at the wrong position or head directly."""
    import jax
    import jax.numpy as jnp
    from repro.models import get_model
    from repro.serve import ServeEngine
    engine = ServeEngine(model, params, MAX_BATCH, MAX_SEQ,
                         decode_chunk=DECODE_CHUNK,
                         prefill_chunk_tokens=PREFILL_CHUNK)
    for r in reqs:
        engine.submit(r)
    sessions, live = [], list(reqs)
    for _ in range(10_000):
        for r in [r for r in live if len(r.out_tokens) >= r.max_new // 2]:
            sessions.append(engine.export_session(r.rid))
            live.remove(r)
        if not live:
            break
        engine.step()
    require(not live, f"{len(live)} requests never reached half their tokens")
    ref_model = get_model(dataclasses.replace(cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        kv_of = jax.jit(lambda p, toks: ref_model.prefill(
            p, {"tokens": toks})[1])
        worst, n = 0.0, 0
        for s in sessions:
            seq = np.concatenate([s.req.prompt,
                                  np.asarray(s.req.out_tokens, np.int32)])
            toks = np.zeros((1, ref_len), np.int32)
            toks[0, :s.pos] = seq[:s.pos]           # causal: padding after
            ref = jax.device_get(kv_of(params, jnp.asarray(toks)))
            for name in ("k", "v"):
                got = s.cache[name].astype(np.float32)     # (L, 1, Hkv, hd, pos)
                want = ref[name][..., :s.pos]
                err = np.linalg.norm(got - want, axis=(2, 3))
                worst = max(worst, float(np.max(
                    err / np.linalg.norm(want, axis=(2, 3)))))
            n += s.pos
    return worst, n


def one_chip(cfg, model, params, seed: int) -> None:
    from repro.serve import ServeEngine
    check_kernels_lowered(model, params)

    dec_err, pre_err = kernel_errors(cfg, seed)
    print(f"kernel vs oracle (bf16, B={MAX_BATCH}, Smax={MAX_SEQ}): "
          f"ragged_decode max_abs_err={dec_err:.3e} "
          f"ragged_prefill max_abs_err={pre_err:.3e} (tol {KERNEL_ATOL})")
    require(dec_err <= KERNEL_ATOL, f"ragged_decode error {dec_err}")
    require(pre_err <= KERNEL_ATOL, f"ragged_prefill error {pre_err}")

    rng = np.random.default_rng(seed)
    lengths = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, MAX_BATCH)
    chunked = make_requests(rng, cfg.vocab, lengths, [MAX_NEW] * MAX_BATCH)
    t0 = time.perf_counter()
    serve(ServeEngine(model, params, MAX_BATCH, MAX_SEQ,
                      decode_chunk=DECODE_CHUNK,
                      prefill_chunk_tokens=PREFILL_CHUNK), chunked)
    print(f"chunked prefill: served {len(chunked)} requests, prompt lengths "
          f"{sorted(int(n) for n in lengths)}, {MAX_NEW} tokens each "
          f"(smoke timing, not a benchmark: wall_s="
          f"{time.perf_counter() - t0:.2f})")

    kv_err, kv_n = session_kv_errors(cfg, model, params, clone(chunked),
                                     REF_LEN)
    print(f"session K/V vs float32 reference over {kv_n} positions x "
          f"{cfg.n_layers} layers: max_rel_err={kv_err:.4f} (tol {KV_RTOL})")
    require(kv_err <= KV_RTOL, f"session K/V relative error {kv_err:.4f}")

    two = rng.choice(np.arange(PROMPT_MIN, PROMPT_MAX + 1), 2, replace=False)
    whole = make_requests(rng, cfg.vocab, [two[0], two[1]] * 2, [MAX_NEW] * 4,
                          rid0=len(chunked))
    t0 = time.perf_counter()
    serve(ServeEngine(model, params, MAX_BATCH, MAX_SEQ,
                      decode_chunk=DECODE_CHUNK), whole)
    print(f"whole-prompt prefill: served {len(whole)} requests, prompt "
          f"lengths {sorted(int(n) for n in two)} x2, {MAX_NEW} tokens each "
          f"(smoke timing, not a benchmark: wall_s="
          f"{time.perf_counter() - t0:.2f})")

    gaps, margins = teacher_forced_gaps(cfg, params, chunked + whole, REF_LEN)
    distinct = len({t for r in chunked + whole for t in r.out_tokens})
    print(f"teacher-forced float32 reference over {gaps.size} tokens: "
          f"agree={int((gaps == 0).sum())} max_gap={gaps.max():.4f} "
          f"(margin {LOGIT_MARGIN}); reference top1-top2 "
          f"min={margins.min():.4f} median={np.median(margins):.4f}; "
          f"distinct generated tokens={distinct}")
    require(gaps.max() <= LOGIT_MARGIN,
            f"engine token trails the float32 argmax by {gaps.max():.4f}")


# -- four chips ----------------------------------------------------------------

def fleet4(cfg, model, params, seed: int) -> None:
    import jax
    from repro.router import FleetGateway, FleetRouter, SLOPolicy
    from repro.serve import ServeEngine
    devices = jax.devices()
    require(len(devices) >= 4, f"--fleet4 needs 4 chips, found {len(devices)}")
    engine = lambda p: ServeEngine(model, p, MAX_BATCH, MAX_SEQ,
                                   decode_chunk=DECODE_CHUNK,
                                   prefill_chunk_tokens=PREFILL_CHUNK)
    replicas = [engine(jax.device_put(params, d)) for d in devices[:4]]
    rng = np.random.default_rng(seed)
    n = 32
    reqs = make_requests(rng, cfg.vocab,
                         rng.integers(PROMPT_MIN, PROMPT_MAX + 1, n),
                         rng.integers(8, 2 * MAX_NEW + 1, n))
    # compile each replica's programs before the router sees its latency
    for e in replicas:
        serve(e, clone(reqs[:1]))
    warm = [e.stats()["requests_served"] for e in replicas]

    t0 = time.perf_counter()
    gw = FleetGateway(replicas,
                      router=FleetRouter(4, slo=SLOPolicy.unlimited()))
    fleet_reqs = clone(reqs)
    for r in fleet_reqs:
        gw.submit(r)
    gw.run_until_drained(max_steps=100_000)
    fleet_s = time.perf_counter() - t0
    streams = {r.rid: list(gw.handle(r.rid).out_tokens) for r in fleet_reqs}
    served = [e.stats()["requests_served"] - w
              for e, w in zip(replicas, warm)]
    homes = [{d for leaf in jax.tree.leaves(e.cache) for d in leaf.devices()}
             for e in replicas]

    t0 = time.perf_counter()
    single = clone(reqs)
    serve(engine(params), single)
    single_s = time.perf_counter() - t0

    print(f"fleet4: served per replica {served}, cache devices "
          f"{[sorted(str(d) for d in h) for h in homes]}")
    print(f"fleet4: 4 replicas wall_s={fleet_s:.2f}, one engine wall_s="
          f"{single_s:.2f} (smoke timings, not benchmark numbers)")
    for i, h in enumerate(homes):
        require(h == {devices[i]}, f"replica {i} cache on {h}, "
                                   f"not {devices[i]}")
    require(all(s > 0 for s in served), f"a replica served nothing: {served}")
    require(sum(served) == n, f"fleet served {sum(served)} of {n}")
    diff = [r.rid for r in single if streams[r.rid] != list(r.out_tokens)]
    require(not diff, f"streams differ from the one-engine run: rids {diff}")
    print(f"fleet4: all {n} token streams identical to the one-engine run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet4", action="store_true",
                    help="four replicas behind the router vs one engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: platform={platform} kind={device['kind']} "
          f"count={device['count']}")

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import get_model
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    t0 = time.perf_counter()

    cfg = get_config(ARCH)
    model = get_model(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(args.seed))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
          f"compute={cfg.compute_dtype}")
    try:
        if args.fleet4:
            fleet4(cfg, model, params, args.seed)
        else:
            one_chip(cfg, model, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"smoke timing, not a benchmark: {meter} "
          f"wall_s={time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
