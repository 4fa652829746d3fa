"""Integration layer — the AXI-wrapper analogue.

The paper wraps its generated hardware module "with an AXI-full interface
and a synthesizable IP core" so the accelerator couples to the host CPU.
On TPU the host/device contract of a generated kernel is instead:

  * a **differentiable** JAX op (``jax.custom_vjp``) so the kernel drops
    into a training step, and
  * a **shardable** op (used under ``shard_map``/pjit) so the kernel drops
    into the distributed runtime on the production mesh.

``gemm_op`` builds exactly that wrapper around a stagecc-compiled GEMM:
forward = the compiled kernel; backward = two more stagecc GEMMs
(dA = dC @ B^T, dB = A^T @ dC) compiled through the same pipeline — the
whole training-facing surface is generated by the infrastructure.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .pipeline import compile_gemm


@functools.lru_cache(maxsize=64)
def _compiled(m: int, n: int, k: int, schedule: str, backend: str):
    ck = compile_gemm(m, n, k, schedule=schedule,
                      want_jax=(backend != "pallas"),
                      want_pallas=(backend == "pallas"))
    if backend == "xla":
        f = ck.run_jax
        return lambda a, b: f(a, b)[0]
    fp = ck.run_pallas
    if fp is None:
        raise ValueError(f"schedule {schedule} has no pallas emission")
    return fp


def gemm_op(m: int, n: int, k: int, schedule: str = "tpu_mxu",
            backend: str = "xla") -> Callable:
    """Differentiable (m,k)x(k,n) GEMM generated by the stagecc pipeline.

    backend: "xla" (runs anywhere) | "pallas" (Mosaic on an accelerator,
    the interpreter on the CPU backend).
    """
    fwd_fn = _compiled(m, n, k, schedule, backend)

    @jax.custom_vjp
    def op(a, b):
        return fwd_fn(a, b)

    def fwd(a, b):
        return fwd_fn(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        # gradients are themselves stagecc-compiled GEMMs; transposes are
        # layout changes handled by XLA around the generated kernels.
        da_fn = _compiled(m, k, n, schedule, backend)
        db_fn = _compiled(k, n, m, schedule, backend)
        da = da_fn(g.astype(a.dtype), jnp.transpose(b))
        db = db_fn(jnp.transpose(a), g.astype(b.dtype))
        return da.astype(a.dtype), db.astype(b.dtype)

    op.defvjp(fwd, bwd)
    return op


def sharded_gemm_op(mesh, m: int, n: int, k: int, schedule: str = "tpu_mxu",
                    backend: str = "xla",
                    lhs_axis: Optional[str] = "data") -> Callable:
    """The crossbar: run the generated kernel under shard_map with the lhs
    row-sharded over ``lhs_axis`` and the rhs replicated (the canonical
    data-parallel deployment of an accelerator IP block)."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[lhs_axis]
    if m % n_shards:
        raise ValueError(f"m={m} not divisible by {lhs_axis}={n_shards}")
    local = gemm_op(m // n_shards, n, k, schedule, backend)
    return jax.shard_map(local, mesh=mesh,
                     in_specs=(P(lhs_axis, None), P(None, None)),
                     out_specs=P(lhs_axis, None), check_vma=False)
