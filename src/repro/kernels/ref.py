"""Plain jnp oracles for the compiler's kernels and the models' paths.

Tests sweep shapes and dtypes and assert allclose against these:
``gemm_ref`` checks the generated GEMM (``core.pipeline.compile_gemm``),
``attention_ref`` the models' prefill attention
(``models.layers.attention_core``), ``ssd_ref`` the Mamba2 prefill scan
(``models.recurrent._ssd_with_state``) — the same discipline the paper
applies by checking "accurate output matrices" from the generated RTL.
The served decode kernels carry their own jnp forms beside them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gemm_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None) -> jax.Array:
    """Softmax attention oracle.

    q: (Sq, D), k/v: (Sk, D).  ``window`` limits attention to the last
    ``window`` positions (local attention); positions are aligned so that
    query i attends keys [i - window + 1, i] (with the causal offset
    Sk - Sq applied when lengths differ).
    """
    Sq, D = q.shape
    Sk = k.shape[0]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    logits = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return (probs @ v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, D: jax.Array | None = None) -> jax.Array:
    """Mamba-2 SSD (state-space dual) recurrence, naive sequential oracle.

    x : (S, H, P)   per-head inputs
    dt: (S, H)      softplus-activated step sizes (already positive)
    A : (H,)        negative decay rates (A < 0)
    B : (S, N)      input projections (single group)
    C : (S, N)      output projections
    D : (H,) or None  skip connection
    returns (S, H, P)
    """
    S, H, P = x.shape
    N = B.shape[-1]

    def step(h, inputs):
        x_t, dt_t, B_t, C_t = inputs            # (H,P), (H,), (N,), (N,)
        decay = jnp.exp(dt_t * A)               # (H,)
        # dB_t x_t^T : outer product per head -> (H, P, N)
        dBx = dt_t[:, None, None] * x_t[:, :, None] * B_t[None, None, :]
        h = h * decay[:, None, None] + dBx      # (H, P, N)
        y_t = jnp.einsum("hpn,n->hp", h, C_t)   # (H, P)
        return h, y_t

    h0 = jnp.zeros((H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (x.astype(jnp.float32), dt.astype(jnp.float32),
                                    B.astype(jnp.float32), C.astype(jnp.float32)))
    if D is not None:
        ys = ys + D[None, :, None] * x.astype(jnp.float32)
    return ys.astype(x.dtype)


def rglru_ref(x: jax.Array, a_gate: jax.Array, i_gate: jax.Array,
              a_param: jax.Array, c: float = 8.0) -> jax.Array:
    """RG-LRU (RecurrentGemma) oracle.

    x, a_gate, i_gate: (S, D) — inputs and pre-sigmoid gates;
    a_param: (D,) — the learnable recurrence parameter (pre-softplus).
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
    with log a_t = -c * softplus(a_param) * sigmoid(a_gate_t).
    """
    log_a = -c * jax.nn.softplus(a_param)[None, :] * jax.nn.sigmoid(
        a_gate.astype(jnp.float32))
    a = jnp.exp(log_a)
    gated = jax.nn.sigmoid(i_gate.astype(jnp.float32)) * x.astype(jnp.float32)
    mult = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9))

    def step(h, inp):
        a_t, gx_t, m_t = inp
        h = a_t * h + m_t * gx_t
        return h, h

    h0 = jnp.zeros((x.shape[1],), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (a, gated, mult))
    return hs.astype(x.dtype)


def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
            ).astype(x.dtype)
