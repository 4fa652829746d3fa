"""Kernels and the models' paths, in allclose sweeps against the
pure-jnp oracles in ``ref.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pipeline import compile_gemm
from repro.kernels import ref
from repro.models.layers import _DIRECT_LIMIT, attention_core
from repro.models.recurrent import _ssd_with_state


# ---- GEMM (stagecc-generated) ---------------------------------------------


def _pick_tile(dim: int, pref: int = 128) -> int:
    """The largest tile of at most ``pref`` that divides ``dim``."""
    t = min(pref, dim)
    while dim % t:
        t -= 1
    return t


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 64),
                                   (64, 192, 256), (96, 96, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_shapes_dtypes(shape, dtype):
    """The generated GEMM, as the kernel cell compiles it
    (``compile_gemm(...).run_pallas``), against the oracle."""
    m, n, k = shape
    rng = np.random.default_rng(sum(shape))
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    ck = compile_gemm(m, n, k, schedule="tpu_mxu_kgrid",
                      dtype=jnp.dtype(dtype).name,
                      tile={"m": _pick_tile(m), "n": _pick_tile(n),
                            "k": _pick_tile(k)},
                      want_jax=False)
    assert ck.run_pallas is not None, ck.pallas_error
    got = np.asarray(ck.run_pallas(a, b)).astype(np.float32)
    want = np.asarray(ref.gemm_ref(a, b))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


# ---- prefill attention (models.layers.attention_core) ------------------------


def _attention(q, k, v, causal=True, window=None, **blocks):
    """``attention_core`` on head-batched (H, S, d) operands, laid out
    as the model calls it: (1, S, H, d), query i at position
    i + Sk - Sq."""
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[None] + (sk - sq)
    kpos = jnp.arange(sk)[None]
    out = attention_core(*(x.transpose(1, 0, 2)[None] for x in (q, k, v)),
                         qpos, kpos, None, causal, window, **blocks)
    return out[0].transpose(1, 0, 2)


def _attention_ref(q, k, v, causal=True, window=None):
    return jax.vmap(lambda qq, kk, vv: ref.attention_ref(
        qq, kk, vv, causal=causal, window=window))(q, k, v)


@pytest.mark.parametrize("sq,sk,d,causal,window", [
    (128, 128, 64, True, None),
    (128, 128, 64, False, None),
    (64, 128, 32, True, 32),
    (256, 256, 64, True, 128),
    (128, 256, 128, True, None),
])
def test_flash_attention_vs_ref(sq, sk, d, causal, window):
    rng = np.random.default_rng(sq + sk + d)
    q = jnp.asarray(rng.standard_normal((3, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, sk, d)), jnp.float32)
    got = _attention(q, k, v, causal, window)
    want = _attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(bq=st.sampled_from([256, 512, 1024]),
       bk=st.sampled_from([256, 512, 1024]))
def test_flash_attention_block_invariance(bq, bk):
    """At a size past ``_DIRECT_LIMIT`` attention runs blockwise (an
    online softmax over KV blocks in a scan over Q blocks); its output
    must not depend on the block sizes, and matches the oracle."""
    s, d = 2048, 32
    assert s * s > _DIRECT_LIMIT
    rng = np.random.default_rng(42)
    q, k, v = (jnp.asarray(rng.standard_normal((2, s, d)), jnp.float32)
               for _ in range(3))
    blockwise = jax.jit(lambda q, k, v: _attention(
        q, k, v, block_q=bq, block_k=bk))
    assert "scan" in str(jax.make_jaxpr(blockwise)(q, k, v))
    a = blockwise(q, k, v)
    b = _attention(q, k, v, block_q=s, block_k=s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(a),
                               np.asarray(_attention_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((2, 128, 64)), jnp.bfloat16)
    got = np.asarray(_attention(q, k, v)).astype(np.float32)
    want = np.asarray(_attention_ref(q, k, v)).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


# ---- prefill SSD (models.recurrent._ssd_with_state) --------------------------


def _ssd_inputs(S, H, P, N, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((S, H, P)), jnp.float32),
            jnp.asarray(np.abs(rng.standard_normal((S, H))) * 0.1, jnp.float32),
            jnp.asarray(-np.abs(rng.standard_normal(H)), jnp.float32),
            jnp.asarray(rng.standard_normal((S, N)), jnp.float32),
            jnp.asarray(rng.standard_normal((S, N)), jnp.float32),
            jnp.asarray(rng.standard_normal(H), jnp.float32))


def _ssd(x, dt, A, B, C, D, chunk, h0=None):
    """``_ssd_with_state`` on one sequence: (y, final state)."""
    y, h = _ssd_with_state(x[None], dt[None], A, B[None], C[None], D,
                           None if h0 is None else h0[None], chunk)
    return y[0], h[0]


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_pallas_vs_naive(chunk):
    """The Mamba2 prefill scan against the sequential oracle; a prompt
    prefilled in two pieces, the second from the first's final state,
    gives the same output."""
    x, dt, A, B, C, D = _ssd_inputs(128, 4, 16, 8)
    want = np.asarray(ref.ssd_ref(x, dt, A, B, C, D))
    got, _ = _ssd(x, dt, A, B, C, D, chunk)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-4)
    head, h = _ssd(*(a[:48] for a in (x, dt)), A, B[:48], C[:48], D, chunk)
    tail, _ = _ssd(*(a[48:] for a in (x, dt)), A, B[48:], C[48:], D, chunk,
                   h0=h)
    np.testing.assert_allclose(np.concatenate([head, tail]), want,
                               rtol=1e-3, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(S=st.sampled_from([32, 64, 128]), H=st.sampled_from([1, 2, 4]),
       P=st.sampled_from([8, 16]), N=st.sampled_from([4, 8]))
def test_ssd_chunked_hypothesis(S, H, P, N):
    x, dt, A, B, C, D = _ssd_inputs(S, H, P, N, seed=S + H + P)
    want = np.asarray(ref.ssd_ref(x, dt, A, B, C, D))
    got, _ = _ssd(x, dt, A, B, C, D, chunk=16)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-4)


def test_ssd_chunk_invariance():
    """Chunk size is a schedule choice — results must be identical."""
    x, dt, A, B, C, D = _ssd_inputs(128, 2, 8, 4, seed=11)
    a, ha = _ssd(x, dt, A, B, C, D, chunk=16)
    b, hb = _ssd(x, dt, A, B, C, D, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(hb), rtol=1e-4,
                               atol=1e-5)


# ---- RG-LRU oracle sanity -----------------------------------------------------


def test_rglru_ref_decays():
    S, D = 32, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((S, D)), jnp.float32)
    ag = jnp.full((S, D), 10.0)          # strong gate -> a ~ exp(-8*softplus)
    ig = jnp.full((S, D), 10.0)          # input gate ~ 1
    a_param = jnp.full((D,), 5.0)
    h = ref.rglru_ref(x, ag, ig, a_param)
    assert np.isfinite(np.asarray(h)).all()
    # with a ~ 0, h_t ~ x_t (no memory): check correlation
    np.testing.assert_allclose(np.asarray(h[5:]), np.asarray(x[5:]),
                               atol=2e-2)


# ---- decode attention ---------------------------------------------------------


def test_decode_attention_vs_ref():
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    rng = np.random.default_rng(7)
    B, KV, rep, hd, Smax = 3, 2, 4, 32, 512
    q = jnp.asarray(rng.standard_normal((B, KV, rep, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, Smax, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, Smax, hd)), jnp.float32)
    valid = jnp.asarray([17, 256, 511], jnp.int32)
    got = decode_attention(q, k, v, valid, block_k=128)
    want = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("valid0", [1, 100, 512])
def test_decode_attention_valid_boundaries(valid0):
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    rng = np.random.default_rng(valid0)
    q = jnp.asarray(rng.standard_normal((1, 1, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 512, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 512, 16)), jnp.float32)
    valid = jnp.asarray([valid0], jnp.int32)
    got = decode_attention(q, k, v, valid, block_k=256)
    want = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _decode_operands(rng, n, B, KV=2, rep=4, hd=32, Smax=512):
    shape = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (shape(n, B, KV, rep, hd), shape(n, B, KV, Smax, hd),
            shape(n, B, KV, Smax, hd))


def test_decode_attention_vmap_folds_into_one_call():
    """Under vmap the kernel runs once, on the mapped axis folded into
    its batch axis; each element matches the oracle, from one valid row
    to a full cache."""
    from repro.kernels.decode_attention import (decode_attention_ref,
                                                decode_attention_vmappable)
    rng = np.random.default_rng(11)
    q, k, v = _decode_operands(rng, 4, 2)
    valid = jnp.asarray([[1, 512], [17, 256], [512, 1], [300, 129]],
                        jnp.int32)
    f = jax.vmap(decode_attention_vmappable)
    got = f(q, k, v, valid)
    for i in range(4):
        np.testing.assert_allclose(
            np.asarray(got[i]),
            np.asarray(decode_attention_ref(q[i], k[i], v[i], valid[i])),
            rtol=2e-5, atol=2e-5)
    kernels = _named_calls(jax.make_jaxpr(f)(q, k, v, valid).jaxpr,
                           "decode_attention")
    assert len(kernels) == 1
    assert kernels[0].invars[1].aval.shape == (8, 2, 512, 32)


def _named_calls(jaxpr, name):
    """The ``jit`` calls named ``name`` anywhere in ``jaxpr``."""
    found = []
    for e in jaxpr.eqns:
        if e.params.get("name") == name:
            found.append(e)
            continue
        for p in e.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                found += _named_calls(sub, name)
    return found


@pytest.mark.parametrize("in_axes", [(0, 0, 0, None), (0, None, None, 0),
                                     (None, 0, 0, None)])
def test_decode_attention_vmap_broadcasts_unmapped(in_axes):
    """Operands the vmap does not map (a ``valid`` shared by every
    element, a cache shared by every query) are broadcast to the folded
    batch; a nested vmap folds again."""
    from repro.kernels.decode_attention import (decode_attention_ref,
                                                decode_attention_vmappable)
    rng = np.random.default_rng(5)
    q, k, v = _decode_operands(rng, 3, 2)
    valid = jnp.asarray([[64, 512], [1, 200], [511, 3]], jnp.int32)
    args = [a if ax == 0 else a[0] for a, ax in zip((q, k, v, valid),
                                                    in_axes)]
    got = jax.vmap(decode_attention_vmappable, in_axes=in_axes)(*args)
    nested = jax.vmap(jax.vmap(decode_attention_vmappable,
                               in_axes=in_axes))(
        *(a[None] for a in args))[0]
    for i in range(3):
        one = [a[i] if ax == 0 else a for a, ax in zip(args, in_axes)]
        want = np.asarray(decode_attention_ref(*one))
        np.testing.assert_allclose(np.asarray(got[i]), want, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(nested[i]), want, rtol=2e-5,
                                   atol=2e-5)


# ---- SSD state step ----------------------------------------------------------


@pytest.mark.parametrize("heads,headdim,d_state,slots,batch", [
    (8, 16, 16, 3, 1),         # the reduced mamba2 the CPU tests serve
    (24, 64, 128, 3, 1),       # mamba2_130m's heads, as the engine steps them
    (24, 64, 128, 2, 16),      # a batch wider than one block of sequences
])
def test_ssd_state_step_vs_ref(heads, headdim, d_state, slots, batch):
    """The kernel (interpreted here) steps one layer of the stack as the
    jnp formula does and leaves every other layer bit-unchanged; under
    vmap over slots it runs once, on the slot axis folded into its group
    axis, and each slot's answer is the unbatched call's."""
    from repro.kernels.ssd_state_step import (ssd_state_step,
                                              ssd_state_step_ref)
    rng = np.random.default_rng(heads + d_state + batch)
    L, layer, W = 3, 2, heads * headdim
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    state = f32(slots, L, batch, d_state, W)
    decay = jnp.repeat(jnp.exp(-0.1 * jnp.abs(f32(slots, batch, heads))),
                       headdim, axis=-1)
    dtx, bmat, cmat = (0.1 * f32(slots, batch, W), f32(slots, batch, d_state),
                       f32(slots, batch, d_state))
    args = (state, decay, dtx, bmat, cmat)
    step = lambda s, *a: ssd_state_step(s, layer, *a)
    y, new = jax.vmap(step)(*args)

    want_y, want = jax.vmap(lambda s, *a: ssd_state_step_ref(s, layer, *a))(
        *args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[:, layer]),
                               np.asarray(want[:, layer]),
                               rtol=1e-6, atol=1e-6)
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(np.asarray(new[:, others]),
                                  np.asarray(state[:, others]))
    for i in range(slots):
        one_y, one = step(*(a[i] for a in args))
        np.testing.assert_array_equal(np.asarray(one_y), np.asarray(y[i]))
        np.testing.assert_array_equal(np.asarray(one), np.asarray(new[i]))
    calls = _named_calls(jax.make_jaxpr(jax.vmap(step))(*args).jaxpr,
                         "ssd_state_step")
    assert len(calls) == 1
    assert calls[0].invars[-1].aval.shape == (slots, L, batch, d_state, W)


_SHARDED_DECODE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config, reduced
from repro.distributed.sharding import axis_rules
from repro.launch.mesh import make_mesh
from repro.models import layers as L

cfg = reduced(get_config("qwen2_7b"))
N, B, Smax = 3, 2, 256
KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
keys = jax.random.split(jax.random.PRNGKey(0), 4)
p = L.init_attention(cfg, L.Maker("init", keys[0]))
x = jax.random.normal(keys[1], (N, B, 1, cfg.d_model))
cache = {n: jax.random.normal(kk, (N, B, KV, Smax, hd))
         for n, kk in zip("kv", keys[2:])}
kv_len = jnp.asarray([0, 100, Smax - 1], jnp.int32)   # valid 1 .. Smax

def step(backend):
    def one(x, c, n):
        pos = n + jnp.zeros((B, 1), jnp.int32)
        return L.apply_attention(p, x, cfg, pos, cache=c, kv_len=n,
                                 backend=backend)
    return jax.jit(jax.vmap(one))

want, want_c = step("xla")(x, cache, kv_len)
mesh = make_mesh((2, 2), ("data", "model"))
with axis_rules(mesh):
    sharded = step("pallas")
    got, got_c = sharded(x, cache, kv_len)
    text = sharded.lower(x, cache, kv_len).as_text()
assert "shard_map" in text or "sdy.manual_computation" in text, "no shard_map"
err = float(jnp.abs(got - want).max())
assert err < 1e-5, err
for n in "kv":
    np.testing.assert_array_equal(np.asarray(got_c[n]), np.asarray(want_c[n]))
print("OK", err)
"""


def test_decode_attention_vmap_under_shard_map():
    """The vmap rule holds inside the ``shard_map`` that
    ``apply_attention`` builds under a mesh: per-slot decode over four
    host devices (batch and kv heads split) matches the XLA path."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SHARDED_DECODE],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.startswith("OK"), r.stdout
