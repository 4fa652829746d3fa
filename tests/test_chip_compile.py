"""The main path's kernels, compiled for a described TPU v5e at real widths.

Nothing runs: each test lowers a kernel through Mosaic for one chip of a
``v5e:2x2`` topology that is described, not attached, and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``) under
its own name, which is what a profile of the chip shows it as.  This
catches what interpret mode cannot: blocks that break the TPU's tiling
rule, primitives Mosaic does not lower, and kernels over their VMEM
limit.  Widths are qwen2_7b's (d_model 3584, d_ff 18944, 28 query / 4 KV
heads of 128).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest

import repro.kernels.decode_attention as decode_mod
import repro.kernels.ssd_state_step as ssd_mod
from repro.configs.base import get_config, reduced
from repro.core import frontend as fe
from repro.core.pipeline import compile_gemm, compile_traced
from repro.kernels.decode_attention import decode_attention
from repro.models.model import Model, RunConfig
from repro.serve.continuous import ContinuousEngine


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


def _kernel_names(compiled):
    """The instruction names of the compiled program's Mosaic kernels:
    ``%decode_attention.7 = ... custom-call(...)`` -> ``decode_attention``."""
    return [line.split(" = ", 1)[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line]


def test_generated_gemm_qwen2_mlp(one_chip):
    ck = compile_gemm(256, 18944, 3584, schedule="tpu_mxu_kgrid",
                      interpret=False)
    assert ck.run_pallas is not None, ck.pallas_error
    c = _compile(ck.run_pallas, [((256, 3584), jnp.float32),
                                 ((3584, 18944), jnp.float32)], one_chip)
    assert _kernel_names(c) == ["gemm_256x18944x3584_none"]


def test_generated_flash_head128(one_chip):
    s, d = 512, 128
    ck = compile_traced(fe.flash_attention_graph(s, s, d), interpret=False)
    assert ck.run_pallas is not None, ck.pallas_error
    c = _compile(ck.run_pallas, [((s, d), jnp.float32),
                                 ((d, s), jnp.float32),
                                 ((s, d), jnp.float32),
                                 ((s, s), jnp.float32)], one_chip)
    # one Mosaic kernel per top-level nest of the graph, named for it;
    # a profile keeps about 64 characters of ``program:kernel``, and the
    # names differ within them
    names = _kernel_names(c)
    assert sorted(names) == [f"nest{i}_flash_{s}x{s}x{d}"
                             for i in range(len(ck.run_pallas.stages))]
    cut = {f"{ck.run_pallas.__name__}:{n}"[:64] for n in names}
    assert len(cut) == len(names) == 5


def test_decode_attention_qwen2_shapes(one_chip):
    B, KV, rep, hd, smax = 4, 4, 7, 128, 1024
    fn = lambda q, k, v, valid: decode_attention(q, k, v, valid,
                                                 interpret=False)
    c = _compile(fn, [((B, KV, rep, hd), jnp.bfloat16),
                      ((B, KV, smax, hd), jnp.bfloat16),
                      ((B, KV, smax, hd), jnp.bfloat16),
                      ((B,), jnp.int32)], one_chip)
    assert _kernel_names(c) == ["decode_attention"]


def test_decode_attention_named_in_batched_step(one_chip, monkeypatch):
    """Inside the serving engine's decode step (a vmap over slots of a
    scan over layers) the kernel runs once per layer for all slots; it
    keeps its name there too, where pallas's own batching would call it
    ``closed_call``."""
    monkeypatch.setattr(decode_mod, "pallas_interpret",
                        lambda interpret=None: False)      # Mosaic
    model = Model(reduced(get_config("qwen2_7b")),
                  RunConfig(backend="pallas", max_seq=96))
    eng = ContinuousEngine(model, None, slots=3, max_len=96)
    put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    args = jax.tree.map(put, (model.param_shapes(), eng._stacked, eng._tok,
                              jnp.ones(3, bool), eng._keys))
    c = jax.jit(eng._batched_step).lower(*args).compile()
    assert _kernel_names(c) == ["decode_attention"]


def _elements(shape: str) -> int:
    return math.prod(int(n) for n in shape.split(",") if n)


def _compile_batched_step(model, slots, max_len, sharding):
    """The serving engine's decode step over ``slots`` slots, compiled
    from shapes alone; and the stacked cache's shapes."""
    eng = ContinuousEngine.__new__(ContinuousEngine)       # nothing allocated
    eng.model, eng.max_len, eng.temperature = model, max_len, 0.0
    eng.mesh = None
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    stacked = jax.tree.map(
        lambda l: put(jax.ShapeDtypeStruct((slots,) + l.shape, l.dtype)),
        model.cache_shapes(1, max_len))
    args = (jax.tree.map(put, model.param_shapes()), stacked,
            put(jax.ShapeDtypeStruct((slots, 1), jnp.int32)),
            put(jax.ShapeDtypeStruct((slots,), bool)),
            put(jax.ShapeDtypeStruct((slots, 2), jnp.uint32)))
    c = jax.jit(eng._batched_step, donate_argnums=(1,)).lower(*args).compile()
    return c, stacked


def _writers_of(lines, size):
    """(op, operand shapes, line) of every instruction of the compiled
    program with an output of ``size`` elements."""
    shapes = {}                                 # instruction -> output shapes
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ", line)
        if m:
            shapes[m[1]] = re.findall(r"\[([\d,]*)\]", m[2])
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (?:\(.*?\)|\S+) ([\w-]+)\((.*)",
                     line)
        if m and any(_elements(s) == size for s in shapes[m[1]]):
            operands = re.findall(r"%([\w.-]+)", m[3].split(")")[0])
            yield m[2], [shapes.get(o, [""])[0] for o in operands], line


_PASSED_ALONG = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")


def test_batched_step_keeps_cache_in_place(one_chip, monkeypatch):
    """The decode step at ``qwen2_7b.decode_heavy``'s own shapes (7 layers,
    32 slots, 4096 rows, bf16) copies no K or V stack: it writes each new
    row where the stack lies and reads one layer per kernel call.

    Passing the cache through the layer scan as ``xs``/``ys`` under the
    slot vmap cost four whole-stack copies a step (a relayout at entry, a
    slice in and a write out per layer, a per-slot slice for the kernel)
    and 2.3 GB of temporaries."""
    monkeypatch.setattr(decode_mod, "pallas_interpret",
                        lambda interpret=None: False)      # Mosaic
    slots, max_len = 32, 4096
    cfg = dataclasses.replace(get_config("qwen2_7b"), num_layers=7)
    model = Model(cfg, RunConfig(param_dtype="bfloat16",
                                 activation_dtype="bfloat16",
                                 cache_dtype="bfloat16", backend="pallas",
                                 max_seq=max_len))
    c, stacked = _compile_batched_step(model, slots, max_len, one_chip)

    assert c.memory_analysis().temp_size_in_bytes <= 300_000_000
    stack = stacked["scan"]["pos0"]["attn"]["k"]
    row = slots * cfg.num_kv_heads * cfg.resolved_head_dim  # a row per slot
    lines = c.as_text().splitlines()
    for op, operands, line in _writers_of(lines, stack.size):
        if op == "dynamic-update-slice":        # in place: what it writes
            assert _elements(operands[1]) <= row, line[:200]
        else:                                   # passed along, not copied
            assert op in _PASSED_ALONG, line[:200]

    calls = [l for l in lines if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1                      # once per layer, in the scan
    # operands: valid, q, k, v; k holds every slot of one layer
    operands = re.findall(r"\w+\[([\d,]*)\]", calls[0].split(
        "operand_layout_constraints=", 1)[1].split("}}", 1)[0])
    assert operands[2] == f"{slots},{cfg.num_kv_heads},{max_len},128"


def test_batched_step_keeps_ssd_state_in_place(one_chip, monkeypatch):
    """The decode step at ``mamba2_130m.chat_bursty``'s shapes (24 layers,
    128 slots, ``max_len`` 1024, bf16, float32 state) copies no state
    stack: one ``ssd_state_step`` call a layer, in the scan, steps every
    slot's state where the stack lies, and nothing else writes it.

    Passing the 2.4-GB state through the layer scan as ``xs``/``ys`` cost
    a whole-stack relayout at entry, a write per layer and a third read
    for the readout, and 2.4 GB of temporaries."""
    monkeypatch.setattr(ssd_mod, "pallas_interpret",
                        lambda interpret=None: False)      # Mosaic
    slots, max_len = 128, 1024
    model = Model(get_config("mamba2_130m"),
                  RunConfig(param_dtype="bfloat16",
                            activation_dtype="bfloat16",
                            cache_dtype="bfloat16", backend="pallas",
                            max_seq=max_len))
    c, stacked = _compile_batched_step(model, slots, max_len, one_chip)

    assert c.memory_analysis().temp_size_in_bytes <= 300_000_000
    state = stacked["scan"]["pos0"]["ssd"]["state"]
    assert state.shape == (slots, 24, 1, 128, 1536)
    lines = c.as_text().splitlines()
    for op, _, line in _writers_of(lines, state.size):
        assert op in _PASSED_ALONG or (
            op == "custom-call" and "ssd_state_step" in line), line[:200]
    assert _kernel_names(c) == ["ssd_state_step"]
    # the call lies in the body of the scan's while loop
    body = None
    for line in lines:
        if re.match(r"\S", line):
            body = line.split()[0].lstrip("%")
        elif "tpu_custom_call" in line:
            break
    assert re.search(rf"while\(.*body=%{re.escape(body)}\b", "\n".join(lines))


def test_batched_step_on_four_chips(topo, monkeypatch):
    """``qwen2_7b_28l.decode_4chip``'s decode step: the whole 28 layers
    at 64 slots x 4096 rows, bf16, on ``data=1, model=4`` of the described
    v5e:2x2.  Each chip holds a quarter of the weights and one KV head of
    the cache (under 8 GiB of its 16 GB), the decode kernel runs under
    ``shard_map`` on that one head of every slot, and the only
    collectives are the layers' all-reduces and the sampling's small
    gathers: no stack is copied or moved between chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import axis_rules, tree_shardings
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(decode_mod, "pallas_interpret",
                        lambda interpret=None: False)      # Mosaic
    slots, max_len = 64, 4096
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    model = Model(get_config("qwen2_7b"),
                  RunConfig(param_dtype="bfloat16",
                            activation_dtype="bfloat16",
                            cache_dtype="bfloat16", backend="pallas",
                            max_seq=max_len))
    eng = ContinuousEngine.__new__(ContinuousEngine)       # nothing allocated
    eng.model, eng.max_len, eng.temperature, eng.mesh = \
        model, max_len, 0.0, mesh
    put = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    rep = NamedSharding(mesh, P())
    shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((slots,) + l.shape, l.dtype),
        model.cache_shapes(1, max_len))
    axes = jax.tree.map(lambda a: "- " + a, model.cache_axes(1, max_len))
    args = (jax.tree.map(put, model.param_shapes(), tree_shardings(
                model.param_axes(), model.param_shapes(), mesh)),
            jax.tree.map(put, shapes, tree_shardings(axes, shapes, mesh)),
            put(jax.ShapeDtypeStruct((slots, 1), jnp.int32), rep),
            put(jax.ShapeDtypeStruct((slots,), bool), rep),
            put(jax.ShapeDtypeStruct((slots, 2), jnp.uint32), rep))
    with axis_rules(mesh):
        c = jax.jit(eng._batched_step, donate_argnums=(1,)).lower(
            *args).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes < 8 * 2**30
    assert mem.temp_size_in_bytes <= 300_000_000
    text = c.as_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1                      # once per layer, in the scan
    operands = re.findall(r"\w+\[([\d,]*)\]", calls[0].split(
        "operand_layout_constraints=", 1)[1].split("}}", 1)[0])
    assert operands[2] == f"{slots},1,{max_len},128"    # one KV head here
    kinds = set(re.findall(
        r"= \S+ (all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)[\w-]*\(", text))
    assert kinds == {"all-reduce", "all-gather"}, kinds
