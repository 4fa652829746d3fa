"""Gradient compression: int8 quantised all-reduce with error feedback.

Distributed-optimisation trick for scale: data-parallel gradient
all-reduces move ~4 bytes/param/step; per-tensor-scaled int8 cuts that
4x on the wire.  Error feedback (residual carried to the next step)
keeps SGD convergence unbiased in expectation.

Implemented as an explicit ``shard_map`` collective so the quantised
representation actually crosses the ICI (a plain with_sharding_constraint
would let XLA all-reduce in f32).  Opt-in via TrainConfig.grad_compress.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` of int8-quantised x (inside shard_map)."""
    q, scale = quantize_int8(x)
    # int8 payloads sum in int32 to avoid overflow across replicas;
    # scales are tiny and reduce in f32.
    s = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    # all replicas must agree on a scale: use the max scale
    smax = jax.lax.pmax(scale, axis_name)
    return s.astype(jnp.float32) * smax / n


def make_compressed_allreduce(mesh: Mesh, axis: str = "data"):
    """Returns f(local_grads_tree) -> mean-reduced tree, communicating
    int8.  Gradients must be replicated over the other mesh axes."""

    def reduce_tree(tree):
        def one(x):
            fn = jax.shard_map(
                functools.partial(compressed_psum_mean, axis_name=axis),
                mesh=mesh, in_specs=P(*(axis,) + (None,) * (x.ndim - 1)),
                out_specs=P(*(axis,) + (None,) * (x.ndim - 1)),
                check_vma=False)
            return fn(x)
        return jax.tree.map(one, tree)

    return reduce_tree


def error_feedback_update(grads, residual):
    """g' = g + r;  r' = g' - Q(g') applied leaf-wise (int8)."""
    def one(g, r):
        g = g.astype(jnp.float32) + r
        q, scale = quantize_int8(g)
        deq = dequantize_int8(q, scale)
        return deq, g - deq
    pairs = jax.tree.map(one, grads, residual)
    new_grads = jax.tree.map(lambda p: p[0], pairs,
                             is_leaf=lambda x: isinstance(x, tuple))
    new_res = jax.tree.map(lambda p: p[1], pairs,
                           is_leaf=lambda x: isinstance(x, tuple))
    return new_grads, new_res
