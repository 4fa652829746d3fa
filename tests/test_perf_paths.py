"""Optimised execution paths (§Perf) must be exact vs their baselines:
absorbed MLA, shard_map expert-parallel MoE, bf16 attention probs."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.distributed.sharding import axis_rules
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_mod
from repro.models.layers import attention_core, set_attention_options
from repro.models.model import Model, RunConfig


@pytest.fixture(autouse=True)
def _reset_knobs():
    yield
    moe_mod.set_moe_impl("auto")
    set_attention_options(probs_dtype="float32", block_q=512, block_k=1024)


def test_absorbed_mla_equals_nonabsorbed():
    """Decode (absorbed, latent-MQA) must match teacher forcing
    (non-absorbed reconstruction) bit-for-bit up to f32 roundoff."""
    cfg = reduced(get_config("deepseek_v2_236b"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    m = Model(cfg, RunConfig(max_seq=32))
    p = m.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                              cfg.vocab_size)
    full, _, _ = m.apply(p, toks)
    cache = m.cache_init(2, 32)
    pre, cache, _ = m.apply(p, toks[:, :8], cache=cache)
    errs = [float(jnp.abs(pre - full[:, :8]).max())]
    for t in range(8, 12):
        lg, cache, _ = m.apply(p, toks[:, t:t + 1], cache=cache)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 2e-4


def _moe_model():
    cfg = reduced(get_config("kimi_k2_1t"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, capacity_factor=8.0))
    return Model(cfg, RunConfig(max_seq=32)), cfg


def test_shardmap_moe_matches_gspmd():
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs multiple devices (run via XLA_FLAGS host count)")
    model, cfg = _moe_model()
    params = model.init(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                cfg.vocab_size)
    mesh = make_mesh((n // 2, 2), ("data", "model"))
    moe_mod.set_moe_impl("gspmd")
    with mesh, axis_rules(mesh):
        ref, _, _ = jax.jit(lambda p, t: model.apply(p, t))(params, tokens)
    moe_mod.set_moe_impl("shardmap")
    with mesh, axis_rules(mesh):
        got, _, _ = jax.jit(lambda p, t: model.apply(p, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_shardmap_moe_subprocess_multi_device():
    """Run the cross-impl check under 8 virtual devices.

    This was xfailed from PR 1 to PR 3 (max err ~8.8e-3 > 2e-4).  The
    divergence was root-caused to the *gspmd* path, not shard_map: its
    combine gathered expert outputs through an (E*capacity+1)-row
    concatenate (a trailing trash row for dropped tokens), and GSPMD
    mispartitions that odd-sized computed-index gather under a
    model-sharded mesh — per-token routed contributions came back
    wrong/zeroed while the shard_map path was bit-exact against the
    unsharded oracle.  apply_moe now keeps the dispatch buffer exactly
    E*capacity rows and masks dropped slots explicitly, which is
    bit-exact under partitioning, so the two impls agree to f32
    roundoff and the xfail is gone.  (Capacity drop ordering and psum
    dtype — the original suspects — were ruled out: routing, keep masks
    and the aux loss matched exactly throughout.)"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8';"
        "import dataclasses, jax, jax.numpy as jnp, numpy as np;"
        "from repro.configs.base import get_config, reduced;"
        "from repro.models.model import Model, RunConfig;"
        "from repro.models import moe as moe_mod;"
        "from repro.distributed.sharding import axis_rules;"
        "cfg = reduced(get_config('kimi_k2_1t'));"
        "cfg = dataclasses.replace(cfg, moe=dataclasses.replace("
        "cfg.moe, num_experts=8, top_k=2, capacity_factor=8.0));"
        "m = Model(cfg, RunConfig(max_seq=32));"
        "p = m.init(jax.random.PRNGKey(1));"
        "t = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, "
        "cfg.vocab_size);"
        "from repro.launch.mesh import make_mesh;"
        "mesh = make_mesh((2, 4), ('data', 'model'));"
        "moe_mod.set_moe_impl('gspmd');\n"
        "with mesh, axis_rules(mesh):\n"
        "    a, _, _ = jax.jit(lambda p, t: m.apply(p, t))(p, t)\n"
        "moe_mod.set_moe_impl('shardmap')\n"
        "with mesh, axis_rules(mesh):\n"
        "    b, _, _ = jax.jit(lambda p, t: m.apply(p, t))(p, t)\n"
        "err = float(jnp.abs(a - b).max());"
        "assert err < 2e-4, err;"
        "print('ok', err)")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=repo)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ok" in r.stdout


def test_bf16_probs_error_bounded():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 4096, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4096, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 4096, 2, 32)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(4096)[None], (1, 4096))
    set_attention_options(probs_dtype="float32")
    a = attention_core(q, k, v, pos, pos, None, True, None)
    set_attention_options(probs_dtype="bfloat16")
    b = attention_core(q, k, v, pos, pos, None, True, None)
    err = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    assert err < 2e-2, err


def test_pallas_decode_backend_matches_xla():
    """The model's serving fast path (pallas decode-attention kernel)
    must produce bit-identical logits to the XLA path."""
    cfg = reduced(get_config("qwen2_7b"))
    m_x = Model(cfg, RunConfig(max_seq=32, backend="xla"))
    m_p = Model(cfg, RunConfig(max_seq=32, backend="pallas"))
    params = m_x.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    cache_x = m_x.cache_init(2, 32)
    cache_p = m_p.cache_init(2, 32)
    _, cache_x, _ = m_x.apply(params, toks[:, :8], cache=cache_x)
    _, cache_p, _ = m_p.apply(params, toks[:, :8], cache=cache_p)
    for t in range(8, 12):
        lx, cache_x, _ = m_x.apply(params, toks[:, t:t + 1], cache=cache_x)
        lp, cache_p, _ = m_p.apply(params, toks[:, t:t + 1], cache=cache_p)
        assert float(jnp.abs(lx - lp).max()) < 2e-4
