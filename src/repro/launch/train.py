"""Training launcher.

Examples:
  # CPU-runnable reduced config (this container):
  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 100 --seq-len 128 --global-batch 8 --checkpoint-dir /tmp/ck

  # full config on a real fleet (same code path; mesh axes picked up from
  # the runtime's device count):
  python -m repro.launch.train --arch qwen2-7b --seq-len 4096 \
      --global-batch 256 --steps 100000 --mesh auto
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.data.pipeline import DataConfig, Pipeline
from repro.device import enable_compile_cache
from repro.distributed.sharding import axis_rules, sharding_for, tree_shardings
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model, RunConfig
from repro.optim import schedule as sched
from repro.optim.optimizer import adamw
from repro.train.step import (TrainConfig, init_state, make_train_step,
                              state_axes, state_shapes)
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default=None, choices=[None, "cosine", "wsd"])
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "single", "multi"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    run = RunConfig(max_seq=args.seq_len, remat=args.remat)
    model = Model(cfg, run)

    sname = args.schedule or ("wsd" if cfg.name.startswith("minicpm")
                              else "cosine")
    lr = sched.make(sname, peak=args.lr, warmup_steps=max(args.steps // 20, 1),
                    total_steps=args.steps)
    optimizer = adamw(lr, weight_decay=0.01)
    step_fn = make_train_step(model, optimizer,
                              TrainConfig(microbatches=args.microbatches))

    mesh = None
    if args.mesh == "host":
        mesh = make_host_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    pipe = Pipeline(DataConfig(vocab_size=cfg.vocab_size,
                               seq_len=args.seq_len,
                               global_batch=args.global_batch,
                               seed=args.seed))

    key = jax.random.PRNGKey(args.seed)
    print(f"[train] arch={cfg.name} params={model.param_count():,} "
          f"mesh={args.mesh} steps={args.steps}")

    if mesh is not None:
        with mesh, axis_rules(mesh):
            st_shapes = state_shapes(model, optimizer)
            st_axes = state_axes(model, optimizer)
            st_sh = tree_shardings(st_axes, st_shapes, mesh)
            jstep = jax.jit(step_fn, in_shardings=(st_sh, None),
                            out_shardings=(st_sh, None),
                            donate_argnums=(0,))
            state = jax.jit(lambda k: init_state(model, optimizer, k),
                            out_shardings=st_sh)(key)
            trainer = Trainer(TrainerConfig(
                total_steps=args.steps,
                checkpoint_every=args.checkpoint_every,
                checkpoint_dir=args.checkpoint_dir), jstep, pipe)
            trainer.install_preemption_handler()
            trainer.run(state)
    else:
        jstep = jax.jit(step_fn, donate_argnums=(0,))
        state = init_state(model, optimizer, key)
        trainer = Trainer(TrainerConfig(
            total_steps=args.steps,
            checkpoint_every=args.checkpoint_every,
            checkpoint_dir=args.checkpoint_dir), jstep, pipe)
        trainer.install_preemption_handler()
        state = trainer.run(state)
        losses = [m["loss"] for m in trainer.metrics_history]
        if losses:
            print(f"[train] loss first->last: {losses[0]:.4f} -> "
                  f"{losses[-1]:.4f} over {len(losses)} steps")


if __name__ == "__main__":
    main()
