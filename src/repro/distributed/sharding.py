"""Logical-axis sharding: DP/FSDP/TP/EP/SP on the production mesh.

Tensors (params and activations) are annotated with *logical* axis names;
rules map logical names to mesh axes.  The resolver enforces
divisibility: a mesh axis that does not divide the tensor dimension is
dropped (documented fallback — e.g. 10 attention heads on a 16-way
``model`` axis stay replicated while d_ff still shards).  This keeps
every (arch × shape × mesh) cell compilable; the roofline table then
exposes the cost of any fallback.

Logical axes used across the framework:
    batch      — global batch            -> ("pod", "data")
    kv_seq     — KV-cache sequence       -> sequence-sharding for long ctx
    heads      — attention query heads   -> "model" (Megatron TP)
    kv_heads   — KV heads                -> "model"
    ff         — MLP hidden              -> "model"
    vocab      — embedding/logits vocab  -> "model"
    experts    — MoE experts             -> "model" (expert parallelism)
    fsdp       — parameter dim for ZeRO-3-style sharding -> ("pod", "data")
    embed/None — replicated
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Tuple[str, ...]]

DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "kv_seq": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "fsdp": ("pod", "data"),
    "embed": (),
    "seq": (),
}

_ctx = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def current_rules() -> Rules:
    return getattr(_ctx, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    """Activate a mesh + rule set for ``shard()`` constraints within."""
    prev = (getattr(_ctx, "mesh", None), getattr(_ctx, "rules", DEFAULT_RULES))
    _ctx.mesh = mesh
    _ctx.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def _resolve_axis(logical: Optional[str], dim: int, mesh: Mesh,
                  rules: Rules, used: set) -> Optional[Union[str, Tuple[str, ...]]]:
    """Map one logical axis to mesh axes with divisibility fallback."""
    if logical is None or logical == "":
        return None
    mesh_axes = rules.get(logical)
    if mesh_axes is None:
        return None
    mesh_axes = tuple(a for a in mesh_axes
                      if a in mesh.shape and a not in used)
    # greedy prefix: keep the longest prefix whose product divides dim
    while mesh_axes:
        prod = 1
        for a in mesh_axes:
            prod *= mesh.shape[a]
        if prod and dim % prod == 0:
            break
        mesh_axes = mesh_axes[:-1]
    if not mesh_axes:
        return None
    used.update(mesh_axes)
    return mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]


def pspec_for(axes: Sequence[Optional[str]], shape: Sequence[int],
              mesh: Mesh, rules: Optional[Rules] = None) -> P:
    rules = dict(DEFAULT_RULES, **(rules or {}))
    used: set = set()
    parts = [_resolve_axis(a, d, mesh, rules, used)
             for a, d in zip(axes, shape)]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def parse_axes(axes: Union[str, Sequence[Optional[str]]]):
    """"fsdp ff" -> ("fsdp", "ff"); "-" entries mean replicated."""
    if isinstance(axes, str):
        return tuple(None if a in ("-", "_") else a for a in axes.split())
    return tuple(axes)


def sharding_for(axes: Union[str, Sequence[Optional[str]]],
                 shape: Sequence[int], mesh: Mesh,
                 rules: Optional[Rules] = None) -> NamedSharding:
    ax = parse_axes(axes)
    if len(ax) != len(shape):
        raise ValueError(f"axes {ax} rank != shape {tuple(shape)}")
    return NamedSharding(mesh, pspec_for(ax, shape, mesh, rules))


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain an activation's sharding under the active mesh (no-op
    when no mesh context is active, e.g. CPU smoke tests)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = pspec_for(axes, x.shape, mesh, current_rules())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(axes_tree, shape_tree, mesh: Mesh,
                   rules: Optional[Rules] = None):
    """Zip an axes tree (string leaves) with a ShapeDtypeStruct tree into a
    NamedSharding tree (for jit in_shardings / checkpoint layouts)."""
    return jax.tree.map(
        lambda ax, sds: sharding_for(ax, sds.shape, mesh, rules),
        axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, str))


def per_device_bytes(tree) -> int:
    """Bytes of ``tree``'s arrays that one device holds: each leaf's shard
    (the whole leaf where it is replicated), from its sharding alone."""
    return sum(math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))
