"""Training launcher: end-to-end driver with checkpoint/restart, straggler-
aware elastic hooks, and deterministic resumable data.

CPU-scale runs use --reduced (or --layers/--d-model overrides); the same
driver drives pod runs when real devices exist (shardings come from the
logical-axis rules + the production mesh).

Examples:
    python -m repro.launch.train --arch smollm-135m --reduced --steps 200
    python -m repro.launch.train --arch smollm-135m --reduced --steps 200 \
        --resume --ckpt-dir /tmp/ck       # restart-from-checkpoint
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..checkpoint import AsyncCheckpointer, latest_step, load_checkpoint
from ..configs import ARCH_IDS, get_config
from ..data import DataConfig, SyntheticLMData
from ..distributed.elastic import StragglerRebalancer
from ..models import get_model
from ..optim.adamw import AdamWConfig
from ..train.step import make_train_step, train_state_init


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-dcn", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    over = {}
    if args.layers:
        over["n_layers"] = args.layers
    if args.d_model:
        over["d_model"] = args.d_model
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = get_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                          total_steps=args.steps)

    state, _specs = train_state_init(model, jax.random.PRNGKey(args.seed),
                                     opt_cfg, compress_dcn=args.compress_dcn)
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      microbatches=args.microbatches,
                                      compress_dcn=args.compress_dcn),
                      donate_argnums=0)

    start_step = 0
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.resume and args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state, extra = load_checkpoint(args.ckpt_dir, last, state)
            start_step = extra["data"]["step"]
            print(f"resumed from step {last} (data step {start_step})")

    data = SyntheticLMData(DataConfig(
        vocab=cfg.vocab, global_batch=args.global_batch,
        seq_len=args.seq_len, seed=args.seed), start_step=start_step)

    losses = []
    t0 = time.perf_counter()        # duration base, not a timestamp
    for i in range(start_step, args.steps):
        batch_np = data.batch_at(i)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        if cfg.family == "audio":
            key = jax.random.PRNGKey(i)
            batch = {"frames": jax.random.normal(
                key, (args.global_batch, args.seq_len, cfg.d_model)),
                "labels": batch["labels"] % cfg.vocab}
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.random.normal(
                jax.random.PRNGKey(i), (args.global_batch,
                                        cfg.n_image_tokens, cfg.d_model))
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if i % args.log_every == 0 or i == args.steps - 1:
            tps = args.global_batch * args.seq_len / max(
                1e-9, (time.perf_counter() - t0) / max(1, len(losses)))
            print(f"step {i:5d} loss {loss:.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {tps:,.0f}", flush=True)
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, state, extra={"data": {"step": i + 1}})
    if ckpt:
        ckpt.save(args.steps, state, extra={"data": {"step": args.steps}})
        ckpt.wait()
    data.close()
    return {"final_loss": losses[-1] if losses else None, "losses": losses,
            "state": state}


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
