"""Elastic rescale: checkpoints restore onto a different mesh."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import checkpointer as ck
from repro.configs.base import get_config, reduced
from repro.distributed.sharding import axis_rules, tree_shardings
from repro.launch.mesh import make_mesh
from repro.models.model import Model, RunConfig
from repro.optim.optimizer import adamw
from repro.train.step import init_state, state_axes, state_shapes

cfg = reduced(get_config('qwen2_7b'))
model = Model(cfg, RunConfig(max_seq=32))
opt = adamw(lambda s: 1e-3)

# train-state built and saved on a (4 data x 2 model) mesh
mesh_a = make_mesh((4, 2), ('data', 'model'))
axes = state_axes(model, opt)
shapes = state_shapes(model, opt)
with mesh_a, axis_rules(mesh_a):
    sh_a = tree_shardings(axes, shapes, mesh_a)
    state = jax.jit(lambda k: init_state(model, opt, k),
                    out_shardings=sh_a)(jax.random.PRNGKey(0))
ck.save('{d}', 1, state)

# restore onto a (2 data x 4 model) mesh — the elastic path
mesh_b = make_mesh((2, 4), ('data', 'model'))
with mesh_b, axis_rules(mesh_b):
    sh_b = tree_shardings(axes, shapes, mesh_b)
    restored, extra = ck.restore('{d}', target=state, shardings=sh_b)

for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
leaf = jax.tree.leaves(restored.params)[1]
assert leaf.sharding.mesh.shape == {{'data': 2, 'model': 4}}, leaf.sharding
print('elastic ok')
"""


@pytest.mark.slow
def test_elastic_reshard_across_meshes(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _CODE.format(d=str(tmp_path))],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "elastic ok" in r.stdout
