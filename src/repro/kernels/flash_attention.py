"""Blocked (flash) attention — pallas kernel with explicit VMEM tiling.

TPU-native adaptation of the contraction-heavy hot spot of every
attention arch in the assigned pool.  The schedule is the
"time-multiplexed" one the paper's nested loop embodies, applied at MXU
granularity: KV blocks stream through one resident accumulator/statistics
set (grid revisiting), so VMEM stays constant in sequence length — the
profitable version of datapath reuse on TPU.

Layout: q (BH, Sq, D), k/v (BH, Sk, D); grid = (BH, nq, nkv) with the kv
dimension innermost (sequential revisits of the same q/out block).
Supports causal masking and local windows (gemma3 / recurrentgemma).
Validated in interpret mode against ``ref.attention_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int | None,
                 sq: int, sk: int, block_q: int, block_k: int):
    ikv = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0].astype(jnp.float32)            # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    iq = pl.program_id(1)
    qpos = iq * block_q + jax.lax.iota(jnp.int32, block_q)[:, None] + (sk - sq)
    kpos = ikv * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                       # (bq, bk)
    corr = jnp.exp(m_prev - m_new)               # (bq, 1)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret", "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BH, Sk, D) -> (BH, Sq, D)."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k})")
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(d))
    grid = (bh, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        sq=sq, sk=sk, block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ikv: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ikv: (b, ikv, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ikv: (b, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ikv: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=pallas_interpret(interpret),
    )(q, k, v)
