"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gru-jet --steps 300
    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
        --steps 50 --batch 8 --seq 64 --checkpoint-dir /tmp/ck --resume

Builds the data pipeline, jitted train step (optionally over a host-device
mesh), async checkpointing, and the straggler monitor; resumes from the
latest committed checkpoint when --resume is given.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ShapeConfig, TrainConfig, get_config, get_smoke_config
from repro.data.pipeline import PipelineConfig, SyntheticStream
from repro.distributed.fault_tolerance import StragglerMonitor
from repro.distributed.sharding import ShardCtx
from repro.launch.compile_cache import enable_compile_cache
from repro.train import trainer


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced same-family config (CPU-sized)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--checkpoint-every", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "gru":
        args.seq = cfg.gru.seq_len
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=min(20, args.steps // 10 + 1),
                       total_steps=args.steps, microbatches=args.microbatches,
                       checkpoint_every=args.checkpoint_every, seed=args.seed)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    stream = SyntheticStream(cfg, shape, PipelineConfig(seed=args.seed))
    ctx = ShardCtx()

    state = trainer.init_state(cfg, tcfg, seed=args.seed)
    step_fn = jax.jit(trainer.make_train_step(cfg, tcfg, ctx),
                      donate_argnums=(0,))

    mgr = None
    start = 0
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir, keep=3)
        if args.resume and mgr.latest_step() is not None:
            state = mgr.restore(state)
            start = int(np.asarray(state["step"]))
            print(f"resumed from step {start}")

    strag = StragglerMonitor()
    t_begin = time.time()
    for s in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        strag.record("host0", time.time() - t0)
        if s % args.log_every == 0 or s == args.steps - 1:
            extra = ""
            if "acc" in metrics:
                extra = f" acc={float(metrics['acc']):.3f}"
            print(f"step {s:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e}{extra} "
                  f"({time.time()-t0:.2f}s)", flush=True)
        if mgr and (s + 1) % tcfg.checkpoint_every == 0:
            mgr.save_async(state, s + 1)
    if mgr:
        mgr.save(state, args.steps)
        mgr.wait()
    print(f"done: {args.steps - start} steps in {time.time()-t_begin:.1f}s; "
          f"final loss {loss:.4f}")
    return state


if __name__ == "__main__":
    main()
