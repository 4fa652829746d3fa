"""Helpers every reference shares: matmul precision, norms and scoring.

``Matmul(fp8=False)`` multiplies in float32 at the highest precision.
``Matmul(fp8=True)`` is the control: each operand is scaled by its own
largest magnitude onto float8_e4m3's range, rounded to it, and the
product is summed in float32 — the step below bfloat16 that a later
change could be tempted by.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0                      # largest finite float8_e4m3fn
PAD = 512                           # sequences are padded to whole blocks


def to_f8(a: jax.Array) -> jax.Array:
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(fp8: bool) -> Callable:
    def mm(spec: str, a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if fp8:
            a, b = to_f8(a), to_f8(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return mm


def rmsnorm(x, w, eps):
    """x * rsqrt(mean(x^2) + eps) * (1 + w): the program stores the gain
    as an offset from 1."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + w.astype(jnp.float32))


def padded(tokens: np.ndarray) -> Tuple[np.ndarray, int]:
    n = len(tokens)
    lp = -(-n // PAD) * PAD
    out = np.zeros(lp, np.int32)
    out[:n] = tokens
    return out, n


def head_scores(x, w_head, targets, vocab: int, x_ctrl=None,
                block: int = 512):
    """Per row of ``x`` (the reference's stream after the final norm): the
    largest reference logit, the reference logit of ``targets``, and —
    given the control's stream ``x_ctrl`` — the reference logit of the
    token the control's own head puts first.  ``w_head`` is (d,
    V_padded); only the first ``vocab`` columns are tokens."""
    w = w_head[:, :vocab]
    ref_mm, ctrl_mm = matmul(False), matmul(True)
    n, d = x.shape
    xc = x if x_ctrl is None else x_ctrl

    def one(args):
        xb, cb, tb = args
        ref = ref_mm("sd,dv->sv", xb, w)
        best = ref.max(-1)
        at = jnp.take_along_axis(ref, tb[:, None], -1)[:, 0]
        if x_ctrl is None:
            return best, at, at
        first = jnp.argmax(ctrl_mm("sd,dv->sv", cb, w), -1)
        return best, at, jnp.take_along_axis(ref, first[:, None], -1)[:, 0]

    blocks = lambda a: a.reshape((n // block, block) + a.shape[1:])
    best, at, ctrl = jax.lax.map(one, (blocks(x), blocks(xc),
                                       blocks(targets)))
    return best.reshape(n), at.reshape(n), ctrl.reshape(n)


def gaps_from(best, at, ctrl, n: int, n_served: int):
    """Gaps of the served tokens: positions n - n_served - 1 .. n - 2
    predict the served tokens n - n_served .. n - 1."""
    lo, hi = n - n_served - 1, n - 1
    best, at, ctrl = (np.asarray(a, np.float64)[lo:hi]
                      for a in (best, at, ctrl))
    return best - at, best - ctrl
