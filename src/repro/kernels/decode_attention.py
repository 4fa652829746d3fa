"""Single-token (decode) attention over a partially-filled KV cache.

The serving hot spot: one query token per sequence attends a (Smax)-deep
cache of which only ``valid`` entries are live.  Blocked over the cache
with online softmax; GQA query groups ride along the sublane dimension
so the (rep x hd) tile feeds the MXU per KV block.

Layout: q (B, KV, rep, hd); k/v (B, KV, Smax, hd) — head-major, the
layout the model's attention cache is stored in, so the kernel reads it
with no transpose; it is also the HBM-friendly layout for decode (each
(b, g) stream is contiguous).  ``valid`` (B,) int32 is prefetched into
SMEM as a scalar operand (a rank-1 VMEM block of one row would break the
TPU's tiling rule).  Grid = (B, KV, nkv); statistics in VMEM scratch
across the kv dimension.  Validated against the pure-jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret

_NEG = -1e30


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_k: int):
    ikv = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (rep, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (rep, bk)

    valid = valid_ref[pl.program_id(0)]
    kpos = ikv * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
    s = jnp.where(kpos < valid, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ikv == nkv - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, block_k: int = 256,
                     interpret: bool | None = None) -> jax.Array:
    """q: (B, KV, rep, hd); k/v: (B, KV, Smax, hd); valid: (B,) int32.
    Returns (B, KV, rep, hd)."""
    B, KV, rep, hd = q.shape
    Smax = k.shape[2]
    if Smax % block_k:
        block_k = Smax               # one block spans a cache of odd depth
    scale = float(1.0 / np.sqrt(hd))
    grid = (B, KV, Smax // block_k)
    # index maps take the prefetched ``valid`` ref as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, g, i, _: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, g, i, _: (b, g, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, g, i, _: (b, g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, g, i, _: (b, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, hd), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, hd), q.dtype),
        interpret=pallas_interpret(interpret),
        name="decode_attention",
    )(valid.astype(jnp.int32), q, k, v)


@jax.custom_batching.custom_vmap
def decode_attention_vmappable(q: jax.Array, k: jax.Array, v: jax.Array,
                               valid: jax.Array) -> jax.Array:
    """``decode_attention`` with its defaults, batched under ``vmap`` by
    :func:`_vmap_fold_batch`."""
    return decode_attention(q, k, v, valid)


@decode_attention_vmappable.def_vmap
def _vmap_fold_batch(axis_size, in_batched, q, k, v, valid):
    """One kernel call for the whole mapped axis: it is folded into the
    kernel's batch axis (``axis_size * B`` rows), with unmapped operands
    broadcast to it.

    Pallas's own rule would loop over the mapped axis, one call per
    element on a copy of its slice, since the scalar operand (``valid``)
    is mapped.  The folded call is still named ``decode_attention``, and
    nests: a vmap outside this one folds again."""
    def fold(a, batched):
        if not batched:
            a = jnp.broadcast_to(a, (axis_size,) + a.shape)
        return a.reshape((-1,) + a.shape[2:])

    out = decode_attention_vmappable(
        *(fold(a, b) for a, b in zip((q, k, v, valid), in_batched)))
    return out.reshape((axis_size, -1) + out.shape[1:]), True


def decode_attention_ref(q, k, v, valid):
    """Oracle: per-(b, kv-group) masked softmax attention."""
    B, KV, rep, hd = q.shape
    Smax = k.shape[2]
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bgrh,bgsh->bgrs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    kpos = jnp.arange(Smax)[None, None, None, :]
    s = jnp.where(kpos < valid[:, None, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrs,bgsh->bgrh", p,
                      v.astype(jnp.float32)).astype(q.dtype)
