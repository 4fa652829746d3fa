"""Gradient compression: quantisation error bounds + error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.mesh import make_mesh
from repro.distributed.compression import (dequantize_int8,
                                           error_feedback_update,
                                           make_compressed_allreduce,
                                           quantize_int8)


@settings(max_examples=25, deadline=None)
@given(scale=st.floats(1e-3, 1e3), seed=st.integers(0, 100))
def test_quantize_error_bound(scale, seed):
    """|x - deq(q(x))| <= max|x| / 127 / 2 elementwise (half-step)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(64) * scale, jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    bound = jnp.max(jnp.abs(x)) / 127.0 * 0.5 + 1e-9
    assert float(err.max()) <= float(bound) * 1.001


def test_quantize_preserves_sign_and_zero():
    x = jnp.asarray([0.0, 1.0, -1.0, 0.5])
    q, s = quantize_int8(x)
    d = dequantize_int8(q, s)
    assert float(d[0]) == 0.0
    assert float(d[1]) > 0 and float(d[2]) < 0


def test_error_feedback_accumulates_unquantized_residual():
    g = {"w": jnp.asarray([1.0, 0.001, -0.002])}
    r = {"w": jnp.zeros(3)}
    gq, r2 = error_feedback_update(g, r)
    # residual + quantised must reconstruct g exactly
    np.testing.assert_allclose(np.asarray(gq["w"] + r2["w"]),
                               np.asarray(g["w"]), rtol=1e-6)


def test_error_feedback_converges_in_expectation():
    """Sum over steps of EF-compressed grads tracks the true sum."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(16)
    sent_sum = np.zeros(16)
    r = {"w": jnp.zeros(16)}
    for i in range(50):
        g = {"w": jnp.asarray(rng.standard_normal(16) * 0.01, jnp.float32)}
        true_sum += np.asarray(g["w"])
        gq, r = error_feedback_update(g, r)
        sent_sum += np.asarray(gq["w"])
    # drift bounded by one quantisation residual, not growing with steps
    drift = np.abs(true_sum - sent_sum).max()
    assert drift <= float(jnp.abs(r["w"]).max()) + 1e-6


def test_compressed_allreduce_mean():
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    reduce_fn = make_compressed_allreduce(mesh, "data")
    x = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)
    out = reduce_fn({"g": x})["g"]
    want = np.tile(np.asarray(x).reshape(n, 4).mean(0), (n, 1))
    np.testing.assert_allclose(np.asarray(out), want, rtol=0.02, atol=0.05)
