"""Integration layer (AXI-wrapper analogue): differentiable + shardable
stagecc kernels inside jit/grad/shard_map."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.integrate import gemm_op, sharded_gemm_op
from repro.launch.mesh import make_mesh


def test_custom_vjp_matches_reference():
    op = gemm_op(8, 8, 8, backend="xla")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)

    def loss_op(a, b):
        return jnp.sum(op(a, b) ** 2)

    def loss_ref(a, b):
        return jnp.sum((a @ b) ** 2)

    ga = jax.grad(loss_op, argnums=(0, 1))(a, b)
    gr = jax.grad(loss_ref, argnums=(0, 1))(a, b)
    for x, y in zip(ga, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-4)


def test_pallas_backend_forward():
    op = gemm_op(16, 16, 16, backend="pallas")
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    np.testing.assert_allclose(np.asarray(op(a, b)), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


def test_inside_jit_and_training_step():
    op = gemm_op(4, 4, 4, backend="xla")

    @jax.jit
    def step(w, x):
        def loss(w):
            return jnp.sum(op(x, w))
        g = jax.grad(loss)(w)
        return w - 0.1 * g

    w = jnp.eye(4)
    x = jnp.ones((4, 4))
    w2 = step(w, x)
    assert w2.shape == (4, 4)
    assert not np.allclose(np.asarray(w2), np.eye(4))


def test_sharded_gemm_under_mesh():
    n = len(jax.devices())
    mesh = make_mesh((n, 1), ("data", "model"))
    m = 8 * n
    op = sharded_gemm_op(mesh, m, 8, 8, backend="xla")
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((m, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    with mesh:
        out = jax.jit(op)(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


def test_sharded_gemm_rejects_indivisible():
    n = len(jax.devices())
    mesh = make_mesh((n, 1), ("data", "model"))
    if n == 1:
        pytest.skip("any m divides 1")
    with pytest.raises(ValueError):
        sharded_gemm_op(mesh, n + 1, 8, 8)
