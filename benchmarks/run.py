"""Benchmark aggregator — one section per paper table/figure plus the
framework-level suites.  Prints ``name,us_per_call,derived`` CSV.

  table1   — paper TABLE I (GEMM cycles, nested vs inner-flattened)
  fig3     — paper Fig. 3 (resource consumption vs size)
  train    — reduced-model train-step wall-clock + tokens/s
  roofline — summary over results/dryrun (if present)
"""

from __future__ import annotations

import os
import sys
import time


def _train_bench() -> list:
    import jax
    import numpy as np
    from repro.configs.base import get_config, reduced
    from repro.data.pipeline import DataConfig, Pipeline
    from repro.models.model import Model, RunConfig
    from repro.optim.optimizer import adamw
    from repro.train.step import TrainConfig, init_state, make_train_step

    rows = []
    for arch in ("minicpm_2b", "mamba2_130m", "deepseek_v2_236b"):
        cfg = reduced(get_config(arch))
        model = Model(cfg, RunConfig(max_seq=64))
        opt = adamw(lambda s: 1e-3)
        step = jax.jit(make_train_step(model, opt, TrainConfig()),
                       donate_argnums=(0,))
        pipe = Pipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                   global_batch=4))
        state = init_state(model, opt, jax.random.PRNGKey(0))
        batch = pipe.jax_batch(0)
        state, m = step(state, batch)            # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        reps = 3
        for i in range(reps):
            state, m = step(state, pipe.jax_batch(i + 1))
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / reps * 1e6
        toks = 4 * 32
        rows.append((f"train/{arch}_reduced/step", us,
                     round(toks / (us / 1e6))))
    return rows


def _roofline_rows() -> list:
    import glob
    import json
    rows = []
    for f in sorted(glob.glob("results/dryrun/*__16x16.json")):
        with open(f) as fh:
            r = json.load(fh)
        if r.get("skipped") or r.get("tag"):
            continue
        ro = r["roofline"]
        dom = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        rows.append((f"roofline/{r['arch']}/{r['shape']}/dominant_s",
                     float("nan"), round(dom, 4)))
    return rows


def main() -> None:
    from benchmarks import fig3_resources, pareto, table1_cycles

    print("name,us_per_call,derived")
    sections = [("table1", table1_cycles.run),
                ("fig3", fig3_resources.run),
                ("pareto", pareto.run),
                ("train", _train_bench)]
    for name, fn in sections:
        try:
            for row in fn():
                n, us, d = row
                print(f"{n},{us:.2f},{d}")
        except Exception as e:  # pragma: no cover
            print(f"{name}/ERROR,nan,{type(e).__name__}:{e}",
                  file=sys.stderr)
    if os.path.isdir("results/dryrun"):
        for n, us, d in _roofline_rows():
            print(f"{n},{us:.2f},{d}")


if __name__ == '__main__':
    main()
