"""GEMM kernels — the paper's case study, generated by the stagecc stack.

Unlike a hand-written kernel, ``pallas_gemm`` below is *emitted* by the
compiler infrastructure (frontend -> TensorIR -> LoopIR -> schedule ->
``pl.pallas_call``), so the paper's whole pipeline sits on the hot path.
Both paper schedules exist at MXU-block granularity:

  * ``tpu_mxu_kgrid`` — K on the grid, one (tm, tn) output block
    time-multiplexed across k steps (the "nested" datapath-reuse analogue,
    constant VMEM);
  * ``tpu_mxu``       — K resident in the block, full (tm, K)/(K, tn)
    stripes in VMEM (the "inner-flattened" analogue, VMEM ∝ K).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.pipeline import compile_gemm
from repro.device import pallas_interpret


def _pick_tile(dim: int, pref: int = 128) -> int:
    t = min(pref, dim)
    while dim % t:
        t -= 1
    return t


@functools.lru_cache(maxsize=256)
def _build(m: int, n: int, k: int, schedule: str, dtype: str,
           interpret: bool, tm: int, tn: int, tk: int):
    ck = compile_gemm(m, n, k, schedule=schedule, dtype=dtype,
                      tile={"m": tm, "n": tn, "k": tk},
                      interpret=interpret, want_jax=False, want_pallas=True)
    if ck.run_pallas is None:
        raise RuntimeError(f"stagecc failed to emit pallas for {ck.name}")
    return ck


def pallas_gemm(a: jax.Array, b: jax.Array, *, schedule: str = "tpu_mxu_kgrid",
                interpret: bool | None = None, tile: int = 128) -> jax.Array:
    """(m,k) @ (k,n) through the stagecc-emitted pallas kernel."""
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2, (a.shape, b.shape)
    dtype = {"float32": "float32", "bfloat16": "bfloat16"}.get(
        str(a.dtype), "float32")
    ck = _build(m, n, kdim, schedule, dtype, pallas_interpret(interpret),
                _pick_tile(m, tile), _pick_tile(n, tile), _pick_tile(kdim, tile))
    return ck.run_pallas(a, b)


def gemm_cost(m: int, n: int, k: int, schedule: str = "tpu_mxu_kgrid",
              tile: int = 128):
    """(cycles, resources) of the generated kernel — TABLE I / Fig. 3 terms."""
    ck = _build(m, n, k, schedule, "float32", True,
                _pick_tile(m, tile), _pick_tile(n, tile), _pick_tile(k, tile))
    return ck.cycles, ck.resources
