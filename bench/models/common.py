"""What every model family shares: the program's Model and seeded weights."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.models.model import Model, RunConfig


def run_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed: a key from 32 bits would map seeds
    that differ by 2**32 onto one key."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def program_model(config: Dict, overrides: Dict, widths: Dict):
    """The registry entry with ``overrides`` applied; each of ``widths``
    (field -> the file's value) must already be what the program runs."""
    cfg = dataclasses.replace(get_config(config["registry"]), **overrides)
    for field, want in widths.items():
        got = getattr(cfg, field)
        if got != want:
            raise ValueError(f"{config['name']}: the program's {field} is "
                             f"{got}, the configuration file says {want}")
    run = RunConfig(max_seq=config["max_len"], **config["run"])
    return Model(cfg, run)


def seeded_params(model: Model, key: jax.Array,
                  special: Optional[Callable] = None):
    """The program's parameter tree filled from ``key`` in one jitted call,
    in the dtype it is served in.  A matrix gets N(0, 1/fan_in) with the
    fan-in on its second-to-last axis; a vector N(0, 0.1^2) (norm weights
    enter as 1 + w, so every path carries a non-zero bias or gain).
    Leaves under ``scan`` carry a leading layer axis.  ``special(name,
    shape, key)`` may return a float32 leaf of its own."""
    shapes = model.param_shapes()
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (path, s), k in zip(leaves, keys):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            x = special(name, s.shape, k) if special else None
            if x is None:
                core = s.shape[1:] if name.startswith("scan/") else s.shape
                std = core[-2] ** -0.5 if len(core) >= 2 else 0.1
                x = jax.random.normal(k, s.shape, jnp.float32) * std
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(key)
