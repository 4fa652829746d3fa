#!/usr/bin/env python3
"""Compile every program of the benchmark's cells for a described TPU v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [cell ...]

Nothing runs and no chip is needed: each program the window drives is
lowered at the cell's own shapes for one chip of a ``v5e:2x2`` topology
that is described, not attached.  That refuses what the chip's compiler
would refuse (a kernel over its fast memory, a block off the tiling
rule, a program that does not fit) before a chip call is spent.  It
prints each program's memory analysis.  No number it prints is a device
metric.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cells  # noqa: E402


def _mib(n) -> str:
    return f"{n / 2**20:.0f} MiB"


def _report(name, compiled, t0):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"[rehearse] {name}: compile_s={time.perf_counter() - t0:.1f} "
          f"mosaic_kernels={text.count('tpu_custom_call')} "
          f"args={_mib(mem.argument_size_in_bytes)} "
          f"out={_mib(mem.output_size_in_bytes)} "
          f"temp={_mib(mem.temp_size_in_bytes)}", flush=True)


def rehearse_serving(cell, one_chip):
    from repro.serve.continuous import ContinuousEngine
    import repro.kernels.decode_attention as da
    da.pallas_interpret = lambda interpret=None: False   # Mosaic, not CPU
    fam = cells.family_module("models", cell.config)
    model, slots, max_len = fam.build(cell.config)
    eng = ContinuousEngine.__new__(ContinuousEngine)
    eng.model, eng.max_len, eng.temperature = model, max_len, 0.0
    eng.mesh = None
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(put, model.param_shapes())
    one = model.cache_shapes(1, max_len)
    stacked = jax.tree.map(
        lambda l: put(jax.ShapeDtypeStruct((slots,) + l.shape, l.dtype)), one)
    cache1 = jax.tree.map(put, one)
    key = put(jax.ShapeDtypeStruct((2,), jnp.uint32))
    keys = put(jax.ShapeDtypeStruct((slots, 2), jnp.uint32))
    tok = put(jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    for p in cell.traffic.prompt_set:
        t0 = time.perf_counter()
        toks = put(jax.ShapeDtypeStruct((1, p), jnp.int32))
        c = jax.jit(eng._prefill).lower(params, toks, key).compile()
        _report(f"{cell.name} _prefill P={p}", c, t0)
    t0 = time.perf_counter()
    c = jax.jit(eng._write, donate_argnums=(0, 1, 2)).lower(
        stacked, tok, keys, cache1, put(jax.ShapeDtypeStruct((1,), jnp.int32)),
        key, put(jax.ShapeDtypeStruct((), jnp.int32))).compile()
    _report(f"{cell.name} _write", c, t0)
    t0 = time.perf_counter()
    c = jax.jit(eng._batched_step, donate_argnums=(1,)).lower(
        params, stacked, tok, put(jax.ShapeDtypeStruct((slots,), bool)),
        keys).compile()
    _report(f"{cell.name} _batched_step slots={slots} max_len={max_len}",
            c, t0)


def rehearse_kernels(cell, one_chip):
    from bench.loops import kernel_suite
    for k in kernel_suite.build_kernels(cell.traffic.params, cell.config,
                                          interpret=False):
        t0 = time.perf_counter()
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in k.shapes]
        c = jax.jit(k.fn).lower(*args).compile()
        _report(f"{cell.name} {k.name}", c, t0)


def main(argv) -> int:
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = cells.load_benchmark()
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = cells.load_cell(name, bench)
        if cell.traffic.loop == "kernel_suite":
            rehearse_kernels(cell, one_chip)
        else:
            rehearse_serving(cell, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
