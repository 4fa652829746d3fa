"""Mixture-of-Experts block (deepseek-v2 / kimi-k2 style).

Capacity-based dense dispatch (Mesh-TensorFlow style): routing becomes
one-hot einsum contractions that GSPMD partitions into all-to-alls when
the expert dimension is sharded over the ``model`` axis (expert
parallelism).  Deterministic, differentiable, and analyzable in the
dry-run roofline — at the price of the capacity-overflow approximation
(dropped tokens fall through the residual), which is the standard
trade-off in TPU MoE stacks.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, shard
from repro.models.layers import Maker, Params, rmsnorm

# MoE execution strategy: "auto" picks the shard_map expert-parallel path
# whenever a mesh with a dividing "model" axis is active (the optimized
# path found in §Perf); "gspmd" forces the baseline. Set via
# ``set_moe_impl`` (the dry-run exposes --moe-impl).
_MOE_IMPL = "auto"


def set_moe_impl(impl: str) -> None:
    global _MOE_IMPL
    assert impl in ("auto", "gspmd", "shardmap"), impl
    _MOE_IMPL = impl


def init_moe(cfg, mk: Maker) -> Params:
    d = cfg.d_model
    m = cfg.moe
    gated = cfg.mlp.startswith("gated")
    p = {
        "norm": mk((d,), "embed", init="zeros"),
        # router weights replicated over "model": every expert-parallel
        # rank computes identical routing locally, costing zero collective
        # traffic (§Perf iteration A2)
        "router": mk((d, m.num_experts), "fsdp -"),
        "w_up": mk((m.num_experts, d, m.expert_ff), "experts fsdp ff"),
        "w_down": mk((m.num_experts, m.expert_ff, d), "experts ff fsdp"),
    }
    if gated:
        p["w_gate"] = mk((m.num_experts, d, m.expert_ff), "experts fsdp ff")
    if m.num_shared:
        sf = m.expert_ff * m.num_shared
        p["shared_up"] = mk((d, sf), "fsdp ff")
        p["shared_down"] = mk((sf, d), "ff fsdp")
        if gated:
            p["shared_gate"] = mk((d, sf), "fsdp ff")
    return p


def _local_expert_ffn(h, top_idx, gates, w_gate, w_up, w_down, *,
                      n_experts: int, top_k: int, capacity: int,
                      act, gated: bool, axis: str = "model"):
    """Per-device body of the expert-parallel shard_map (§Perf A1).

    ``h`` (T_loc, d) is this data-shard's tokens, replicated across the
    ``model`` axis; w_* are the LOCAL expert slices (E_loc, d, f).  Each
    model rank serves the tokens routed to its own experts — tokens need
    no exchange at all (they are already resident) and the only
    collective is one psum of the combined output.
    """
    T, d = h.shape
    E_loc = w_up.shape[0]
    rank = jax.lax.axis_index(axis)
    lo = rank * E_loc

    e_flat = top_idx.reshape(-1)                  # (T*K,) global expert ids
    g_flat = gates.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T), top_k)
    e_local = e_flat - lo
    mine = (e_local >= 0) & (e_local < E_loc)
    e_key = jnp.where(mine, e_local, E_loc)       # foreign tokens sort last
    order = jnp.argsort(e_key)
    e_sort = e_key[order]
    t_sort = t_flat[order]
    g_sort = g_flat[order]
    counts = jnp.zeros((E_loc + 1,), jnp.int32).at[e_key].add(1)
    seg_start = jnp.cumsum(counts) - counts
    rank_in_e = jnp.arange(T * top_k) - seg_start[e_sort]
    keep = (e_sort < E_loc) & (rank_in_e < capacity)
    slot = jnp.where(keep, e_sort * capacity + rank_in_e, E_loc * capacity)

    buf = jnp.zeros((E_loc * capacity + 1, d), h.dtype)
    buf = buf.at[slot].set(h[t_sort], mode="drop")
    xin = buf[:E_loc * capacity].reshape(E_loc, capacity, d)
    if gated:
        hid = act(jnp.einsum("ecd,edf->ecf", xin, w_gate)) * \
            jnp.einsum("ecd,edf->ecf", xin, w_up)
    else:
        hid = act(jnp.einsum("ecd,edf->ecf", xin, w_up))
    xout = jnp.einsum("ecf,efd->ecd", hid, w_down)
    xout = jnp.concatenate([xout.reshape(E_loc * capacity, d),
                            jnp.zeros((1, d), xout.dtype)], axis=0)
    contrib = xout[slot] * (g_sort * keep.astype(jnp.float32)
                            )[:, None].astype(xout.dtype)
    y = jnp.zeros((T, d), xout.dtype).at[t_sort].add(contrib)
    return jax.lax.psum(y, axis)


def _shardmap_moe(p, h, cfg, act, gated, top_idx, gates, mesh):
    m = cfg.moe
    T = h.shape[0]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    T_loc = T // n_batch
    E_loc = m.num_experts // mesh.shape["model"]
    capacity = max(1, min(
        int(math.ceil(T_loc * m.top_k * m.capacity_factor / m.num_experts)),
        T_loc))
    body = lambda hh, ti, gg, wg, wu, wd: _local_expert_ffn(
        hh, ti, gg, wg, wu, wd, n_experts=m.num_experts, top_k=m.top_k,
        capacity=capacity, act=act, gated=gated)
    tok_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None)
    w_spec = P("model", None, None)
    wg = p["w_gate"] if gated else p["w_up"]
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
        out_specs=tok_spec, check_vma=False,
    )(h, top_idx, gates, wg, p["w_up"], p["w_down"])


def _can_shardmap(cfg, T: int) -> bool:
    mesh = current_mesh()
    if _MOE_IMPL == "gspmd" or mesh is None or "model" not in mesh.shape:
        return False
    m = cfg.moe
    if m.num_experts % mesh.shape["model"]:
        return False
    n_batch = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n_batch *= mesh.shape[a]
    if T % n_batch:
        return False
    # Measured (§Perf B2): below ~1 routed token per expert the dispatch
    # overhead of the expert-parallel path exceeds its win — decode-sized
    # token counts stay on the dense GSPMD path under "auto".
    if _MOE_IMPL == "auto" and T * m.top_k < m.num_experts:
        return False
    return True


def apply_moe(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).

    Sort-based dispatch: tokens are routed through an (E*C, d) expert
    buffer via scatter/gather with computed slots.  Memory is
    O(T*d + E*C*d) — never the O(T*E*C) of one-hot dispatch tensors,
    which is what keeps the 1M-token train_4k cells of deepseek/kimi
    compilable.  Capacity overflow drops through the residual (standard
    TPU-MoE approximation).

    Under an active mesh the dispatch runs as an explicit expert-parallel
    ``shard_map`` (§Perf iteration A1): GSPMD cannot partition the
    scatter/gather with computed indices and falls back to replicating
    token buffers (baseline: ~118 TB/device of all-reduce on
    kimi-k2 train_4k); the shard_map form needs a single output psum.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.num_experts, m.top_k
    capacity = max(1, int(math.ceil(T * K * m.capacity_factor / E)))
    capacity = min(capacity, T)
    gated = cfg.mlp.startswith("gated")
    act = jax.nn.silu if cfg.mlp == "gated_silu" else jax.nn.gelu

    h = rmsnorm(x, p["norm"], cfg.norm_eps).reshape(T, d)
    logits = jnp.einsum("td,de->te", h, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)

    top_vals, top_idx = jax.lax.top_k(probs, K)                   # (T, K)
    gates = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    sel_frac = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(
        1.0) / (T * K)
    frac_probs = probs.mean(0)
    aux = E * jnp.sum(sel_frac * frac_probs) * m.router_aux_weight

    if _MOE_IMPL in ("auto", "shardmap") and _can_shardmap(cfg, T):
        y = _shardmap_moe(p, h, cfg, act, gated, top_idx,
                          gates.astype(jnp.float32), current_mesh())
        if m.num_shared:
            if gated:
                sh = act(h @ p["shared_gate"]) * (h @ p["shared_up"])
            else:
                sh = act(h @ p["shared_up"])
            y = y + sh @ p["shared_down"]
        y = y.reshape(B, S, d)
        return x + shard(y, "batch", None, None), aux

    # ---- sort-based slot assignment ----
    e_flat = top_idx.reshape(-1)                                  # (T*K,)
    t_flat = jnp.repeat(jnp.arange(T), K)                         # (T*K,)
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat)                                   # stable
    e_sort = e_flat[order]
    t_sort = t_flat[order]
    g_sort = g_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
    seg_start = jnp.cumsum(counts) - counts                       # (E,)
    rank = jnp.arange(T * K) - seg_start[e_sort]
    keep = rank < capacity
    slot = jnp.where(keep, e_sort * capacity + rank, E * capacity)

    # scatter tokens into the expert buffer; dropped tokens carry slot ==
    # E*capacity, one past the end, and fall out via mode="drop".  (An
    # earlier version kept a trash row inside the buffer and gathered
    # back through a (E*capacity+1)-row concatenate; GSPMD mispartitions
    # that odd-sized gather under a model-sharded mesh — the computed-
    # index gather read wrong rows and silently zeroed routed expert
    # contributions, the "gspmd vs shardmap divergence" tracked since
    # PR 1.  Keeping every array exactly E*capacity rows and masking
    # with ``keep`` is bit-exact under partitioning.)
    buf = jnp.zeros((E * capacity, d), h.dtype)
    buf = buf.at[slot].set(h[t_sort], mode="drop")
    xin = shard(buf.reshape(E, capacity, d), "experts", None, None)

    if gated:
        hid = act(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    else:
        hid = act(jnp.einsum("ecd,edf->ecf", xin, p["w_up"]))
    hid = shard(hid, "experts", None, "ff")
    xout = jnp.einsum("ecf,efd->ecd", hid, p["w_down"])           # (E, C, d)
    flat = xout.reshape(E * capacity, d)

    # gather back (clamped index + explicit keep mask, see above) and
    # combine with gates
    contrib = jnp.where(keep[:, None], flat[jnp.where(keep, slot, 0)], 0.0)
    contrib = contrib * (g_sort * keep.astype(jnp.float32)
                         )[:, None].astype(xout.dtype)
    y = jnp.zeros((T, d), xout.dtype).at[t_sort].add(contrib)

    if m.num_shared:
        if gated:
            sh = act(h @ p["shared_gate"]) * (h @ p["shared_up"])
        else:
            sh = act(h @ p["shared_up"])
        y = y + sh @ p["shared_down"]

    y = y.reshape(B, S, d)
    return x + shard(y, "batch", None, None), aux
