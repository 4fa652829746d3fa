"""One replica over four devices: ``RunConfig(model_parallel=4)``.

The model builds its ``(data=1, model=4)`` mesh, its parameters are
drawn in place on it, and ``ContinuousEngine`` serves on it with no mesh
passed.  On four host devices (a subprocess, since the device count is
fixed when JAX starts), at a tiny qwen2 shape whose heads, KV heads, MLP
width and vocabulary all divide by four, in float32:

* the benchmark's ``qwen2_mesh`` weights equal ``seeded_params`` value
  for value;
* logits through prefill and cached decode (the decode batched over
  slots, the pallas kernel under ``shard_map``) match the plain float32
  reference, ``bench/refs/qwen2``;
* the engine's greedy token streams equal the same model's at
  ``model_parallel=1``, and with prompts prefilled in pieces.

At Qwen2-7B's real widths the sharding rules put every weight but the
norms on ``model``, with no fall-back to replicated.
"""

import json
import os
import subprocess
import sys

import pytest
from jax.sharding import AbstractMesh

from repro.configs.base import get_config
from repro.distributed.sharding import pspec_for
from repro.models.model import Model, RunConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both sides compute in float32 and differ only in the order of their
# sums (partial sums over four devices, the kernel's online softmax):
# relative differences of ~1e-6.  A bfloat16 step (2^-8 ~ 4e-3) would
# fail this limit.
LOGITS_TOL = 1e-4

_SCRIPT = r"""
import dataclasses, json, sys
sys.path[0:0] = [%(repo)r, %(src)r]
import jax, jax.numpy as jnp, numpy as np
from bench import cells
import bench.models.common as common
from bench.models import qwen2_mesh
from bench.models.common import run_key, seeded_params
from bench.refs import qwen2 as ref
from bench.refs.common import matmul, padded, rmsnorm
from repro.configs.base import get_config
from repro.distributed.sharding import axis_rules
from repro.serve.continuous import ContinuousEngine, Request

TOY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
           num_attention_heads=8, num_key_value_heads=4, head_dim=16,
           vocab_size=256, slots=2, max_len=64)
common.get_config = lambda name: dataclasses.replace(
    get_config(name), d_model=64, d_ff=128, num_heads=8, num_kv_heads=4,
    head_dim=16, vocab_size=256)


def config(mp):
    c = dict(cells._json("configs", "qwen2_7b_28l"), **TOY)
    c["run"] = dict(param_dtype="float32", activation_dtype="float32",
                    cache_dtype="float32", backend="pallas",
                    model_parallel=mp)
    return c


out = {}
c4, c1 = config(4), config(1)
m4, slots, max_len = qwen2_mesh.build(c4)
m1, _, _ = qwen2_mesh.build(c1)
key = run_key(7)
p4 = qwen2_mesh.init_params(m4, c4, key)
p1 = seeded_params(m1, key)
out["mesh"] = dict(m4.mesh.shape)
out["mesh_1"] = m1.mesh is None
out["same_values"] = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p1)))
out["specs"] = {
    "/".join(str(getattr(k, "key", k)) for k in path): list(a.sharding.spec)
    for path, a in jax.tree_util.tree_flatten_with_path(p4)[0]}

# logits through prefill, then cached decode vmapped over two slots
V = c4["vocab_size"]
prompt = np.random.default_rng(3).integers(0, V, 9).astype(np.int32)
n_dec = 6


def run(params, tokens):
    cache = m4.cache_init(1, max_len)
    lp, cache, _ = m4.apply(params, tokens, cache=cache)
    rows = [lp[0, :, :V]]
    toks = jnp.argmax(lp[:, -1:, :V], -1).astype(jnp.int32)      # (1, 1)
    served = [toks[0, 0]]
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), cache)
    toks = jnp.stack([toks, toks])
    step = jax.vmap(lambda c, t: m4.apply(params, t, cache=c)[:2])
    for _ in range(n_dec):
        ld, stacked = step(stacked, toks)                   # (2, 1, 1, V)
        rows.append(ld[0, 0, :, :V])
        toks = jnp.argmax(ld[:, :, -1:, :V], -1).astype(jnp.int32)
        served.append(toks[0, 0, 0])
    return jnp.concatenate(rows), jnp.stack(served)


with axis_rules(m4.mesh):
    got, served = jax.jit(run)(p4, jnp.asarray(prompt[None]))
got, served = np.asarray(got), np.asarray(served)
seq = np.concatenate([prompt, served]).astype(np.int32)
dims = (c4["num_attention_heads"], c4["num_key_value_heads"],
        c4["head_dim"], c4["rms_norm_eps"], c4["rope_theta"])
with jax.default_matmul_precision("highest"):
    toks, n = padded(seq[:-1])
    x = ref._embed_jit(p4["embed"], jnp.asarray(toks), c4["hidden_size"])
    for i in range(c4["num_hidden_layers"]):
        x = ref._layer_jit(jax.tree.map(lambda a: a[i], p4["scan"]["pos0"]),
                           x, dims, False)
    h = rmsnorm(x, p4["final_norm"], c4["rms_norm_eps"])
    want = matmul(False)("sd,dv->sv", h, p4["lm_head"][:, :V])[:n]
want = np.asarray(want)
out["logits_rows"] = [int(got.shape[0]), int(want.shape[0])]
out["logits_err"] = float(np.abs(got - want).max() / np.abs(want).max())
gap, _ = ref.score(p4, c4, seq, len(served))
out["score_gap"] = float(gap.max())

# the engine on the model's layout against the same model on one device
reqs = [Request(i, np.random.default_rng(10 + i).integers(0, V, p)
                .astype(np.int32), 6) for i, p in enumerate((5, 9, 7))]
streams = []
for model, params in ((m4, p4), (m1, p1)):
    eng = ContinuousEngine(model, params, slots=slots, max_len=max_len)
    if model is m4:
        out["engine_mesh"] = eng.mesh is m4.mesh
        out["cache_spec"] = list(eng._stacked["scan"]["pos0"]["attn"]["k"]
                                 .sharding.spec)
        out["bytes_per_chip"] = [eng.param_bytes_per_chip,
                                 eng.cache_bytes_per_chip]
    else:
        out["bytes_one"] = [eng.param_bytes_per_chip,
                            eng.cache_bytes_per_chip]
    res = eng.serve([Request(r.rid, r.prompt, r.max_new) for r in reqs])
    streams.append({str(k): v.tolist() for k, v in sorted(res.items())})
out["streams"] = streams

# prompts prefilled in pieces on the mesh: a piece's cache comes back laid
# out as it went in, so one program serves each piece length
m4p = type(m4)(m4.cfg, dataclasses.replace(m4.run, prefill_chunk=4))
eng = ContinuousEngine(m4p, p4, slots=slots, max_len=max_len)
res = eng.serve([Request(r.rid, r.prompt, r.max_new) for r in reqs])
out["piecewise"] = {str(k): v.tolist() for k, v in sorted(res.items())}
out["extend_programs"] = eng._extend_one._cache_size()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    src = os.path.join(REPO, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c",
                        _SCRIPT % {"repo": REPO, "src": src}],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=REPO)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = next(l for l in r.stdout.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_the_model_builds_its_mesh_from_the_run(four):
    assert four["mesh"] == {"data": 1, "model": 4}
    assert four["mesh_1"]                   # model_parallel=1: no mesh
    assert four["engine_mesh"]              # the engine takes the model's


def test_mesh_weights_equal_the_seeded_draw(four):
    assert four["same_values"]


def test_every_weight_lies_on_model(four):
    specs = four["specs"]
    for name, spec in specs.items():
        if name.endswith("norm"):
            assert "model" not in spec, (name, spec)
        else:
            assert "model" in spec, (name, spec)
    assert four["cache_spec"].count("model") == 1


def test_sharded_logits_match_the_float32_reference(four):
    assert four["logits_rows"] == [9 + 6, 9 + 6]
    assert four["logits_err"] < LOGITS_TOL
    assert four["score_gap"] < LOGITS_TOL


def test_greedy_streams_equal_one_device(four):
    sharded, one = four["streams"]
    assert sharded == one
    assert all(len(v) == 6 for v in one.values())


def test_piecewise_prefill_on_the_mesh_equals_whole_prompts(four):
    assert four["piecewise"] == four["streams"][0]
    assert four["extend_programs"] == 3          # pieces of 1, 4 and 3


def test_a_chip_holds_a_quarter(four):
    (params4, cache4), (params1, cache1) = four["bytes_per_chip"], \
        four["bytes_one"]
    assert cache4 * 4 == cache1 + 3 * 4 * 2          # 2 replicated lengths
    assert params1 / 4 < params4 < params1 / 3       # norms replicated


def test_real_widths_shard_without_fallback():
    """Qwen2-7B at its published widths on data=1, model=4: every leaf
    that names heads, KV heads, the MLP width or the vocabulary puts that
    axis on ``model`` (the divisibility rule drops none), and the stacked
    cache splits its 4 KV heads one to a chip."""
    model = Model(get_config("qwen2_7b"),
                  RunConfig(param_dtype="bfloat16", max_seq=4096))
    mesh = AbstractMesh((1, 4), ("data", "model"))
    import jax
    axes = jax.tree_util.tree_flatten_with_path(
        model.param_axes(), is_leaf=lambda x: isinstance(x, str))[0]
    shapes = jax.tree.leaves(model.param_shapes())
    sharded = 0
    for (path, ax), s in zip(axes, shapes):
        names = ax.split()
        spec = pspec_for([None if a == "-" else a for a in names], s.shape,
                         mesh)
        split = {"heads", "kv_heads", "ff", "vocab"} & set(names)
        assert ("model" in tuple(spec)) == bool(split), (path, ax, spec)
        sharded += bool(split)
    assert sharded == 12         # wq wk wv wo bq bk bv, 3 MLP, embed, head
    cache = jax.tree.leaves(model.cache_axes(1, 4096),
                            is_leaf=lambda x: isinstance(x, str))
    kv = [a for a in cache if "kv_heads" in a]
    assert len(kv) == 2
    for a in kv:
        spec = pspec_for(("-",) + tuple(None if x == "-" else x
                                       for x in a.split()),
                         (64, 28, 1, 4, 4096, 128), mesh)
        assert spec[3] == "model", spec
