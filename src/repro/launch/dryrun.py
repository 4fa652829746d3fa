import os
# The dry run simulates the production meshes on host devices. It pins
# the CPU backend so that it never takes an attached TPU, and the
# children that ``--all`` spawns inherit both settings.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective analysis.

This is the scale proof for hardware we don't have: a successful
``.lower().compile()`` against the 256-chip single-pod mesh and the
512-chip 2-pod mesh demonstrates that every sharding in the system is
coherent (no mismatched pspecs, no unsupported collectives, no
compile-time OOM), and the compiled artifact yields the roofline terms
reported in EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ARCHS, get_config
from repro.distributed.sharding import axis_rules, sharding_for, tree_shardings
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.models.model import (Model, RunConfig, SHAPES, cell_applicable,
                                input_specs)
from repro.optim import schedule as sched
from repro.optim.optimizer import adamw
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.step import (TrainConfig, make_train_step, state_axes,
                              state_shapes)

FACTORED_THRESHOLD = 5e10      # params above this use factored 2nd moment


def build_optimizer(cfg):
    factored = cfg.param_count() > FACTORED_THRESHOLD
    lr = sched.make("wsd" if cfg.name.startswith("minicpm") else "cosine",
                    peak=3e-4, warmup_steps=2000, total_steps=100_000)
    return adamw(lr, factored=factored,
                 state_dtype=jnp.bfloat16 if factored else jnp.float32)


def _shard_count(sharding, shape) -> int:
    n = 1
    spec = sharding.spec
    mesh = sharding.mesh
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            n *= mesh.shape[a]
    return n


def _bytes_per_device(shapes_tree, shardings_tree) -> int:
    total = 0
    for sds, sh in zip(jax.tree.leaves(shapes_tree),
                       jax.tree.leaves(shardings_tree,
                                       is_leaf=lambda x: hasattr(x, "spec"))):
        n = 1
        for d in sds.shape:
            n *= d
        total += n * sds.dtype.itemsize // max(_shard_count(sh, sds.shape), 1)
    return total


def dryrun_cell(arch: str, shape: str, multi_pod: bool,
                microbatches: int = 1, remat: str = "dots",
                extra_tag: str = "", moe_impl: str = "gspmd",
                attn_probs_dtype: str = "float32",
                block_q: int = 512, block_k: int = 1024,
                mla_absorbed: bool = True) -> Dict[str, Any]:
    from repro.models.layers import set_attention_options
    from repro.models.mla import set_mla_absorbed
    from repro.models.moe import set_moe_impl
    set_moe_impl(moe_impl)
    set_mla_absorbed(mla_absorbed)
    set_attention_options(probs_dtype=attn_probs_dtype, block_q=block_q,
                          block_k=block_k)
    cfg = get_config(arch)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "skipped": True, "reason": why}

    info = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    B, S = info["global_batch"], info["seq_len"]
    run = RunConfig(param_dtype="bfloat16", cache_dtype="bfloat16",
                    max_seq=S, remat=remat if info["kind"] == "train"
                    else "none")
    model = Model(cfg, run)
    kind = info["kind"]
    specs = input_specs(cfg, shape, dtype=jnp.bfloat16)

    t0 = time.perf_counter()
    with mesh, axis_rules(mesh):
        batch_shardings = {
            k: sharding_for(("batch",) + ("-",) * (len(v.shape) - 1),
                            v.shape, mesh)
            for k, v in specs.items()}

        if kind == "train":
            optimizer = build_optimizer(cfg)
            st_shapes = state_shapes(model, optimizer)
            st_axes = state_axes(model, optimizer)
            st_shardings = tree_shardings(st_axes, st_shapes, mesh)
            step_fn = make_train_step(model, optimizer,
                                      TrainConfig(microbatches=microbatches))
            jitted = jax.jit(step_fn,
                             in_shardings=(st_shardings, batch_shardings),
                             out_shardings=(st_shardings, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(st_shapes, specs)
            state_bytes = _bytes_per_device(st_shapes, st_shardings)
        else:
            pshapes = model.param_shapes()
            paxes = model.param_axes()
            pshardings = tree_shardings(paxes, pshapes, mesh)
            cshapes = model.cache_shapes(B, S)
            caxes = model.cache_axes(B, S)
            cshardings = tree_shardings(caxes, cshapes, mesh)
            state_bytes = (_bytes_per_device(pshapes, pshardings)
                           + _bytes_per_device(cshapes, cshardings))
            if kind == "prefill":
                fn = make_prefill_step(model)
                args = (pshapes, cshapes, specs["tokens"])
                in_sh = (pshardings, cshardings, batch_shardings["tokens"])
                if "extra_embeds" in specs:
                    args = args + (specs["extra_embeds"],)
                    in_sh = in_sh + (batch_shardings["extra_embeds"],)
                jitted = jax.jit(fn, in_shardings=in_sh,
                                 out_shardings=(None, cshardings),
                                 donate_argnums=(1,))
                lowered = jitted.lower(*args)
            else:                                   # decode
                fn = make_decode_step(model)
                jitted = jax.jit(
                    fn,
                    in_shardings=(pshardings, cshardings,
                                  batch_shardings["tokens"]),
                    out_shardings=(None, cshardings),
                    donate_argnums=(1,))
                lowered = jitted.lower(pshapes, cshapes, specs["tokens"])
        t_lower = time.perf_counter() - t0

        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

    # ---- analysis ----
    mem: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        for field in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
            if hasattr(ma, field):
                mem[field] = int(getattr(ma, field))
    except Exception as e:                          # pragma: no cover
        mem["error"] = str(e)
    print("memory_analysis:", mem or "n/a")

    cost: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "optimal_seconds")
                    or k.startswith("bytes accessed"))}
    except Exception as e:                          # pragma: no cover
        cost = {"error": str(e)}
    print("cost_analysis:", {k: v for k, v in list(cost.items())[:4]})

    hlo = compiled.as_text()
    stats = H.analyze_hlo_module(hlo)        # trip-count-correct accounting
    coll = stats.collectives

    # analytic model flops
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = B * S
        model_flops = 6.0 * n_active * tokens
    elif kind == "prefill":
        tokens = B * S
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = B
        model_flops = 2.0 * n_active * tokens

    flops_dev = stats.flops
    bytes_dev = stats.bytes
    roof = H.roofline_terms(flops_dev, bytes_dev, coll.total_bytes,
                            model_flops_total=model_flops, n_devices=n_dev)

    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "params": cfg.param_count(),
        "active_params": n_active,
        "tokens_per_step": tokens,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "state_bytes_per_device": int(state_bytes),
        "memory_analysis": mem,
        "cost_analysis_raw": cost,
        "hlo_stats": {"flops": stats.flops, "bytes": stats.bytes,
                      "while_trips": stats.while_trips},
        "collectives": {"counts": coll.counts,
                        "bytes_by_kind": coll.bytes_by_kind,
                        "total_bytes": coll.total_bytes},
        "roofline": roof.as_dict(),
        "tag": extra_tag,
    }
    return rec


def cell_list():
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            ok, _ = cell_applicable(cfg, shape)
            cells.append((arch, shape, ok))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="grad-accum microbatches for train cells (8 keeps "
                         "temp memory within v5e HBM at the baseline)")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--moe-impl", default="gspmd",
                    choices=["auto", "gspmd", "shardmap"])
    ap.add_argument("--attn-probs-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--block-k", type=int, default=1024)
    ap.add_argument("--mla-absorbed", default="on", choices=["on", "off"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--timeout", type=int, default=1800,
                    help="per-cell subprocess timeout (driver mode)")
    args = ap.parse_args()

    if args.list:
        for arch, shape, ok in cell_list():
            print(f"{arch:22s} {shape:12s} {'run' if ok else 'SKIP'}")
        return

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.all:
        # driver mode: one subprocess per cell for isolation
        failures = []
        for arch, shape, ok in cell_list():
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                outfile = os.path.join(args.out, tag + ".json")
                if os.path.exists(outfile):
                    print(f"[skip existing] {tag}")
                    continue
                if not ok:
                    cfgrec = {"arch": arch, "shape": shape, "skipped": True,
                              "mesh": "2x16x16" if mp else "16x16",
                              "reason": cell_applicable(get_config(arch),
                                                        shape)[1]}
                    with open(outfile, "w") as f:
                        json.dump(cfgrec, f, indent=1)
                    print(f"[skip n/a] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", "multi" if mp else "single",
                       "--out", args.out,
                       "--microbatches", str(args.microbatches),
                       "--remat", args.remat, "--tag", args.tag,
                       "--moe-impl", args.moe_impl,
                       "--attn-probs-dtype", args.attn_probs_dtype,
                       "--block-q", str(args.block_q),
                       "--block-k", str(args.block_k)]
                print(f"[dryrun] {tag} ...", flush=True)
                t0 = time.perf_counter()
                try:
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=args.timeout)
                    dt = time.perf_counter() - t0
                    if r.returncode != 0:
                        failures.append(tag)
                        print(f"[FAIL {dt:.0f}s] {tag}\n{r.stdout[-2000:]}"
                              f"\n{r.stderr[-4000:]}")
                    else:
                        print(f"[ok {dt:.0f}s] {tag}")
                except subprocess.TimeoutExpired:
                    failures.append(tag)
                    print(f"[TIMEOUT] {tag}")
        print(f"\ndone; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    # single-cell mode
    assert args.arch and args.shape, "--arch and --shape required"
    for mp in meshes:
        rec = dryrun_cell(args.arch, args.shape, mp,
                          microbatches=args.microbatches, remat=args.remat,
                          extra_tag=args.tag, moe_impl=args.moe_impl,
                          attn_probs_dtype=args.attn_probs_dtype,
                          block_q=args.block_q, block_k=args.block_k,
                          mla_absorbed=(args.mla_absorbed == "on"))
        tag = f"{args.arch}__{args.shape}__{rec.get('mesh', 'na')}"
        if args.tag:
            tag += f"__{args.tag}"
        outfile = os.path.join(args.out, tag + ".json")
        with open(outfile, "w") as f:
            json.dump(rec, f, indent=1)
        if not rec.get("skipped"):
            r = rec["roofline"]
            print(f"[cell] {tag}: compute={r['compute_s']:.4f}s "
                  f"memory={r['memory_s']:.4f}s "
                  f"collective={r['collective_s']:.4f}s "
                  f"bottleneck={r['bottleneck']} "
                  f"useful={r['useful_ratio']:.3f} "
                  f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")


if __name__ == "__main__":
    main()
