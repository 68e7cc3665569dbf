"""Serve a small model with batched requests through the continuous-batching
engine.

    PYTHONPATH=src python examples/serve_lm.py
"""

import time

import numpy as np

import jax

from repro.configs import get_config
from repro.models import get_model
from repro.serve import Request, ServeEngine


def main() -> None:
    cfg = get_config("qwen2-0.5b", reduced=True)
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    engine = ServeEngine(m, params, max_batch=4, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 12), max_new=8)
            for i in range(8)]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    dt = time.perf_counter() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new/dt:.1f} tok/s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt={r.prompt[:6].tolist()}... "
              f"-> {r.out_tokens}")


if __name__ == "__main__":
    main()
