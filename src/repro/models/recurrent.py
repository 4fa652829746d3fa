"""Recurrent blocks: RG-LRU (recurrentgemma/griffin) and SSD (mamba2).

TPU adaptation notes:
  * training-time RG-LRU uses ``jax.lax.associative_scan`` (log-depth,
    VPU-friendly) instead of a sequential loop;
  * training-time SSD uses the chunked matmul decomposition
    (``kernels.ssd_scan``) so the MXU does the work — the paper's
    "GEMM-ification of tensor ops" future-work item;
  * decode is a single recurrence step on cached state (constant memory —
    these are the archs that make the 500k-context cell feasible); the
    SSD state steps in place in the layer scan's carried stack
    (``kernels.ssd_state_step``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh, shard
from repro.kernels import ops as kops
from repro.kernels.ssd_state_step import ssd_state_step, ssd_state_step_ref
from repro.models.layers import Maker, Params, rmsnorm


def causal_conv1d(x: jax.Array, w: jax.Array,
                  state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: (B, S, C), w: (W, C).
    ``state``: (B, W-1, C) previous inputs (decode/prefill continuation).
    Returns (y, new_state)."""
    B, S, C = x.shape
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((B, W - 1, C), x.dtype)
    ext = jnp.concatenate([state, x], axis=1)          # (B, S+W-1, C)
    y = jnp.zeros((B, S, C), jnp.float32)
    for i in range(W):
        y = y + ext[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    new_state = ext[:, -(W - 1):] if W > 1 else jnp.zeros((B, 0, C), x.dtype)
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------
# RG-LRU (griffin recurrent block)
# --------------------------------------------------------------------------


def init_rglru(cfg, mk: Maker) -> Params:
    d = cfg.d_model
    r = cfg.rglru
    w = r.width or d
    return {
        "norm": mk((d,), "embed", init="zeros"),
        "w_x": mk((d, w), "fsdp ff"),
        "w_y": mk((d, w), "fsdp ff"),          # gate branch
        "conv": mk((r.conv_width, w), "- ff"),
        "w_a_gate": mk((w, w), "fsdp ff"),
        "w_i_gate": mk((w, w), "fsdp ff"),
        "a_param": mk((w,), "ff", init="normal", scale=0.5),
        "w_out": mk((w, d), "ff fsdp"),
    }


def _rglru_scan(u: jax.Array, ag: jax.Array, ig: jax.Array,
                a_param: jax.Array, c: float,
                h0: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """u/ag/ig: (B, S, W). Returns (h_seq, h_last)."""
    log_a = -c * jax.nn.softplus(a_param.astype(jnp.float32)) * \
        jax.nn.sigmoid(ag.astype(jnp.float32))
    a = jnp.exp(log_a)                                  # (B, S, W)
    gated = jax.nn.sigmoid(ig.astype(jnp.float32)) * u.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * gated
    if h0 is not None:
        # fold the initial state in as a virtual step at t=-1
        a = jnp.concatenate([jnp.zeros_like(a[:, :1]), a], axis=1)
        b = jnp.concatenate([h0[:, None].astype(jnp.float32), b], axis=1)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    if h0 is not None:
        h = h[:, 1:]
    return h, h[:, -1]


def apply_rglru(p: Params, x: jax.Array, cfg,
                cache: Optional[Params] = None
                ) -> Tuple[jax.Array, Optional[Params]]:
    B, S, d = x.shape
    r = cfg.rglru
    h_in = rmsnorm(x, p["norm"], cfg.norm_eps)
    u = jnp.einsum("bsd,dw->bsw", h_in, p["w_x"])
    u = shard(u, "batch", None, "ff")
    ygate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", h_in, p["w_y"]))

    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = causal_conv1d(u, p["conv"], conv_state)
    ag = jnp.einsum("bsw,wv->bsv", u, p["w_a_gate"])
    ig = jnp.einsum("bsw,wv->bsv", u, p["w_i_gate"])
    h0 = cache["h"] if cache is not None else None
    h, h_last = _rglru_scan(u, ag, ig, p["a_param"], r.c, h0)
    out = jnp.einsum("bsw,wd->bsd", (h * ygate.astype(h.dtype)).astype(x.dtype),
                     p["w_out"])
    new_cache = None
    if cache is not None:
        new_cache = {"h": h_last.astype(cache["h"].dtype), "conv": new_conv}
    return x + shard(out, "batch", None, None), new_cache


def rglru_cache_spec(cfg, batch: int, dtype) -> dict:
    r = cfg.rglru
    w = r.width or cfg.d_model
    return {"h": jax.ShapeDtypeStruct((batch, w), jnp.float32),
            "conv": jax.ShapeDtypeStruct((batch, r.conv_width - 1, w), dtype)}


# --------------------------------------------------------------------------
# SSD / mamba2 block
# --------------------------------------------------------------------------


def init_ssd(cfg, mk: Maker) -> Params:
    d = cfg.d_model
    s = cfg.ssm
    di = s.d_inner or 2 * d
    H = di // s.head_dim
    N = s.state_dim
    return {
        "norm": mk((d,), "embed", init="zeros"),
        "in_proj": mk((d, 2 * di + 2 * N + H), "fsdp ff"),
        "conv": mk((s.conv_width, di + 2 * N), "- ff"),
        "A_log": mk((H,), "-", init="zeros"),
        "D": mk((H,), "-", init="ones"),
        "dt_bias": mk((H,), "-", init="zeros"),
        "out_norm": mk((di,), "ff", init="zeros"),
        "out_proj": mk((di, d), "ff fsdp"),
    }


def _split_ssd(proj: jax.Array, di: int, N: int, H: int):
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * N]
    dt = proj[..., -H:]
    return z, xbc, dt


def apply_ssd(p: Params, x: jax.Array, cfg,
              cache: Optional[Params] = None, backend: str = "xla",
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Optional[Params]]:
    """Mamba2 SSD block.  ``cache`` holds the state (B, N, H*P) and the
    convolution window (B, W-1, C) before this call; with ``layer``, the
    (L, ...) stacks of a layer scan, stepped at ``layer`` where they lie,
    and the returned cache is the whole stack.  Decode (S == 1) steps the
    state with :func:`ssd_state_step`, the Mosaic kernel with ``backend``
    "pallas" off a mesh, else its jnp form."""
    B, S, d = x.shape
    s = cfg.ssm
    di = s.d_inner or 2 * d
    H = di // s.head_dim
    P, N = s.head_dim, s.state_dim

    h_in = rmsnorm(x, p["norm"], cfg.norm_eps)
    proj = jnp.einsum("bsd,dk->bsk", h_in, p["in_proj"])
    z, xbc, dt = _split_ssd(proj, di, N, H)
    conv_state = states = None
    if cache is not None:
        # a single layer's cache is stepped as a stack of one
        states, convs = ((cache["state"], cache["conv"]) if layer is not None
                         else (cache["state"][None], cache["conv"][None]))
        at = jnp.int32(0) if layer is None else layer
        conv_state = jax.lax.dynamic_index_in_dim(convs, at, 0, False)
    xbc, new_conv = causal_conv1d(xbc, p["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(B, S, H, P)
    Bmat = xbc[..., di:di + N]
    Cmat = xbc[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if S == 1 and cache is not None:
        # single-token recurrence (decode)
        decay = jnp.repeat(jnp.exp(dt[:, 0] * A[None, :]), P, axis=-1)
        dtx = (dt[:, 0, :, None] * xs[:, 0]).reshape(B, H * P)
        # a Mosaic kernel is not partitioned: under a mesh, the jnp form
        kernel = backend == "pallas" and current_mesh() is None
        step = ssd_state_step if kernel else ssd_state_step_ref
        y, states = step(states, at, decay, dtx,
                         Bmat[:, 0].astype(jnp.float32),
                         Cmat[:, 0].astype(jnp.float32))
        y = y.reshape(B, H, P) \
            + p["D"].astype(jnp.float32)[None, :, None] * xs[:, 0]
        y = y[:, None].reshape(B, S, di).astype(x.dtype)
    else:
        h0 = None
        if cache is not None:                 # (B, N, H*P) -> (B, H, P, N)
            h0 = jax.lax.dynamic_index_in_dim(states, at, 0, False)
            h0 = h0.reshape(B, N, H, P).transpose(0, 2, 3, 1)
        y, h_fin = _ssd_with_state(xs, dt, A, Bmat, Cmat, p["D"],
                                   h0, s.chunk, backend)
        y = y.reshape(B, S, di)
        if cache is not None:
            h_fin = h_fin.transpose(0, 3, 1, 2).reshape(B, N, H * P)
            states = jax.lax.dynamic_update_index_in_dim(
                states, h_fin.astype(states.dtype), at, 0)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["out_norm"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"])
    new_cache = None
    if cache is not None:
        convs = jax.lax.dynamic_update_index_in_dim(
            convs, new_conv.astype(convs.dtype), at, 0)
        new_cache = ({"state": states, "conv": convs} if layer is not None
                     else {"state": states[0], "conv": convs[0]})
    return x + shard(out, "batch", None, None), new_cache


def _ssd_with_state(xs, dt, A, Bmat, Cmat, D, h0, chunk, backend):
    """Batched chunked SSD that threads an initial/final state.
    xs: (B,S,H,P), dt: (B,S,H), Bmat/Cmat: (B,S,N)."""
    B_, S, H, P = xs.shape
    N = Bmat.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    nc = S // chunk
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def per_batch(xb, dtb, Bb, Cb, h0b):
        xc = xb.reshape(nc, chunk, H, P).astype(jnp.float32)
        dtc = dtb.reshape(nc, chunk, H).astype(jnp.float32)
        Bc = Bb.reshape(nc, chunk, N).astype(jnp.float32)
        Cc = Cb.reshape(nc, chunk, N).astype(jnp.float32)

        def step(h, inp):
            xk, dtk, Bk, Ck = inp
            sl = jnp.cumsum(dtk * A[None, :], axis=0)             # (L, H)
            M = jnp.where(causal[:, :, None],
                          jnp.exp(sl[:, None] - sl[None, :]), 0.0)
            CB = Ck @ Bk.T
            y_intra = jnp.einsum("tuh,tu,uhp->thp", M, CB,
                                 dtk[:, :, None] * xk)
            y_inter = jnp.exp(sl)[:, :, None] * jnp.einsum("tn,hpn->thp", Ck, h)
            w = jnp.exp(sl[-1][None, :] - sl) * dtk
            h_new = (jnp.exp(sl[-1])[:, None, None] * h
                     + jnp.einsum("uhp,un->hpn", w[:, :, None] * xk, Bk))
            return h_new, y_intra + y_inter

        hh = (jnp.zeros((H, P, N), jnp.float32) if h0b is None
              else h0b.astype(jnp.float32))
        h_fin, ys = jax.lax.scan(step, hh, (xc, dtc, Bc, Cc))
        return ys.reshape(S, H, P), h_fin

    if h0 is None:
        f = lambda xb, dtb, Bb, Cb: per_batch(xb, dtb, Bb, Cb, None)
        y, h_fin = jax.vmap(f)(xs, dt, Bmat, Cmat)
    else:
        y, h_fin = jax.vmap(per_batch)(xs, dt, Bmat, Cmat, h0)
    y = y + D[None, None, :, None] * xs.astype(jnp.float32)
    return y.astype(xs.dtype), h_fin


def ssd_cache_spec(cfg, batch: int, dtype) -> dict:
    """The state, float32, state-major as the decode kernel reads it:
    (batch, N, H*P); the convolution window of the last W-1 inputs."""
    s = cfg.ssm
    di = s.d_inner or 2 * cfg.d_model
    return {
        "state": jax.ShapeDtypeStruct((batch, s.state_dim, di), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, s.conv_width - 1,
                                      di + 2 * s.state_dim), dtype),
    }
