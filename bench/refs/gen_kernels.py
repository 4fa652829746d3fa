"""Plain float32 references of the generated kernels, and their control.

* ``gemm``: ``a @ b``;
* ``flash``: softmax attention of one head, ``softmax(q kt + mask) v``
  with ``q`` pre-scaled and an additive mask, as the compiler's
  ``flash_attention_graph`` states it.

With ``fp8`` the same math takes float8_e4m3 operands (each scaled onto
its range) and sums in float32: the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs.common import matmul


def gemm(a, b, fp8: bool = False):
    return matmul(fp8)("mk,kn->mn", a, b)


def flash(q, kt, v, mask, fp8: bool = False):
    mm = matmul(fp8)
    s = mm("sd,dt->st", q, kt) + mask.astype(jnp.float32)
    return mm("st,td->sd", jax.nn.softmax(s, -1), v)


REFS = {"gemm": gemm, "flash": flash}


def rel_err(got, want) -> jax.Array:
    """max|got - want| / max|want|."""
    got = got.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)) / jnp.maximum(
        jnp.max(jnp.abs(want)), 1e-30)
