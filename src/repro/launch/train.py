"""Training launcher: ``python -m repro.launch.train --arch yi-9b ...``

On a real multi-host pod this runs under `jax.distributed.initialize()`
(one process per host; flags below). With ``--reduced`` every width
shrinks (``configs.reduced``) so the whole loop — data pipeline → pjit
train step → checkpoint manager → straggler monitor — runs on a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.data import DataConfig, make_pipeline
from repro.models.lm import build_model
from repro.runtime import StepMonitor
from repro.train import AdamWConfig, make_train_step
from repro.train.trainstep import init_state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink every width (configs.reduced) for CPU "
                         "smoke runs")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=10,
                       total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, ocfg, ctx=None, remat=True),
                      donate_argnums=(0,))

    data = make_pipeline(DataConfig(seq_len=args.seq,
                                    global_batch=args.batch,
                                    vocab_size=cfg.vocab_size))
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    monitor = StepMonitor()

    start = 0
    if args.resume and mgr.latest_step() is not None:
        start, state = mgr.restore()
        print(f"resumed from step {start}")
    else:
        state = init_state(model, jax.random.PRNGKey(0))

    host = f"host{jax.process_index()}"
    for step in range(start, args.steps):
        batch = {"tokens": jnp.asarray(data.batch(step))}
        if cfg.n_prefix_embeds:
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.n_prefix_embeds, cfg.d_model), jnp.float32)
        if cfg.encdec:
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.enc_seq, cfg.d_model), jnp.float32)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        metrics = jax.tree.map(float, jax.device_get(metrics))
        monitor.record(host, time.time() - t0)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {metrics['loss']:.4f}  "
                  f"lr {metrics['lr']:.2e}  gnorm {metrics['grad_norm']:.2f}"
                  f"  {monitor.medians().get(host, 0):.2f}s/step")
        if step and step % args.ckpt_every == 0:
            mgr.save(step, state)
    mgr.save(args.steps, state)
    mgr.wait()
    print(f"done; checkpoints: {mgr.all_steps()}")
    if monitor.stragglers():
        print("stragglers flagged:", monitor.stragglers())
    return state


if __name__ == "__main__":
    main()
