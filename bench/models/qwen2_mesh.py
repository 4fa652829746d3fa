"""qwen2 configurations whose run spans several chips
(``run.model_parallel``): the ``qwen2`` family's model, with its seeded
weights drawn in place on the model's mesh."""

from __future__ import annotations

from typing import Dict

import jax

from bench.models.common import seeded_params
from bench.models.qwen2 import build  # noqa: F401  (the family's build)
from repro.distributed.sharding import tree_shardings


def init_params(model, config: Dict, key):
    """``seeded_params`` under one jit whose outputs lie on the model's
    mesh by the sharding rules: with partitionable threefry each chip
    draws its own shard, and the values are those of ``seeded_params``."""
    shardings = tree_shardings(model.param_axes(), model.param_shapes(),
                               model.mesh)
    return jax.jit(lambda k: seeded_params(model, k),
                   out_shardings=shardings)(key)
