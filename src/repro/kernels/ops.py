"""Jit'd public wrappers for the kernels package.

``backend`` selects the execution path everywhere:
  * "xla"              — pure jnp (runs on any device; the dry-run path)
  * "pallas"           — pallas kernels, lowered through Mosaic on an
                         accelerator and interpreted on the CPU backend
  * "pallas_auto"      — the cost-model-selected generated GEMM
Models take this as config so the same architecture definition runs in
smoke tests, dry-runs, and on hardware.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .gemm import pallas_gemm
from .ssd_scan import ssd_chunked, ssd_scan

BACKENDS = ("xla", "pallas", "pallas_auto")


def matmul(a: jax.Array, b: jax.Array, backend: str = "xla",
           schedule: str = "tpu_mxu_kgrid") -> jax.Array:
    if backend == "xla":
        return ref.gemm_ref(a, b)
    if backend == "pallas_auto":
        # cost-model-selected schedule+tiles (core/autotune.py)
        from repro.core.autotune import compile_gemm_autotuned
        m, k = a.shape
        n = b.shape[1]
        ck = compile_gemm_autotuned(m, n, k, dtype=str(a.dtype)
                                    if str(a.dtype) in ("float32", "bfloat16")
                                    else "float32")
        return ck.run_pallas(a, b)
    return pallas_gemm(a, b, schedule=schedule)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None, backend: str = "xla",
              block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Batched multi-head attention.  q: (..., Sq, D), k/v: (..., Sk, D)."""
    if backend == "xla":
        fn = functools.partial(ref.attention_ref, causal=causal,
                               window=window, scale=scale)
        for _ in range(q.ndim - 2):
            fn = jax.vmap(fn)
        return fn(q, k, v)
    lead = q.shape[:-2]
    qf = q.reshape((-1,) + q.shape[-2:])
    kf = k.reshape((-1,) + k.shape[-2:])
    vf = v.reshape((-1,) + v.shape[-2:])
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          scale=scale, block_q=block_q, block_k=block_k)
    return out.reshape(lead + out.shape[-2:])


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: Optional[jax.Array] = None, *, chunk: int = 64,
        backend: str = "xla") -> jax.Array:
    """SSD scan.  x: (..., S, H, P); dt: (..., S, H); B/C: (..., S, N)."""
    if backend == "xla":
        fn = functools.partial(ssd_chunked, chunk=chunk)
    else:
        fn = functools.partial(ssd_scan, chunk=chunk)
    call = (lambda xx, dd, bb, cc: fn(xx, dd, A, bb, cc, D))
    for _ in range(x.ndim - 3):
        call = jax.vmap(call)
    return call(x, dt, B, C)
