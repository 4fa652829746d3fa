"""The main path's kernels, compiled for a described TPU v5e at real widths.

Nothing runs: each test lowers a kernel through Mosaic for one chip of a
``v5e:2x2`` topology that is described, not attached, and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  This
catches what interpret mode cannot: blocks that break the TPU's tiling
rule, primitives Mosaic does not lower, and kernels over their VMEM
limit.  Widths are qwen2_7b's (d_model 3584, d_ff 18944, 28 query / 4 KV
heads of 128).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import frontend as fe
from repro.core.pipeline import compile_gemm, compile_traced
from repro.kernels.decode_attention import decode_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_generated_gemm_qwen2_mlp(one_chip):
    ck = compile_gemm(256, 18944, 3584, schedule="tpu_mxu_kgrid",
                      interpret=False)
    assert ck.run_pallas is not None, ck.pallas_error
    c = _compile(ck.run_pallas, [((256, 3584), jnp.float32),
                                 ((3584, 18944), jnp.float32)], one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_generated_flash_head128(one_chip):
    s, d = 512, 128
    ck = compile_traced(fe.flash_attention_graph(s, s, d), interpret=False)
    assert ck.run_pallas is not None, ck.pallas_error
    c = _compile(ck.run_pallas, [((s, d), jnp.float32),
                                 ((d, s), jnp.float32),
                                 ((s, d), jnp.float32),
                                 ((s, s), jnp.float32)], one_chip)
    # one Mosaic kernel per top-level nest of the graph
    assert c.as_text().count("tpu_custom_call") == \
        len(ck.run_pallas.stages)


def test_decode_attention_qwen2_shapes(one_chip):
    B, KV, rep, hd, smax = 4, 4, 7, 128, 1024
    fn = lambda q, k, v, valid: decode_attention(q, k, v, valid,
                                                 interpret=False)
    c = _compile(fn, [((B, KV, rep, hd), jnp.bfloat16),
                      ((B, KV, smax, hd), jnp.bfloat16),
                      ((B, KV, smax, hd), jnp.bfloat16),
                      ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in c.as_text()
