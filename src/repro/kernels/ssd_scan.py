"""Mamba-2 SSD (state-space duality) chunked scan — pallas kernel.

This realises the paper's future-work item (3): extending the GEMM-
centric compiler story to "tensor operations for machine learning".  The
SSD decomposition rewrites a linear recurrence as chunked *matmuls*
(MXU-friendly) plus a tiny inter-chunk state recurrence — i.e. the same
time-multiplexed-GEMM schedule the paper studies, applied to an SSM.

Math (per head h, chunk of length L, state dim N, head dim P):
    s_t   = cumsum(dt_t * A)                       (log-decay within chunk)
    y_t   = exp(s_t) * (C_t · h_in)                      [inter-chunk]
          + sum_{u<=t} exp(s_t - s_u) dt_u (C_t·B_u) x_u [intra, matmuls]
    h_out = exp(s_L) h_in + Σ_u exp(s_L - s_u) dt_u B_u x_u^T

Grid = (H, n_chunks); the chunk dimension iterates innermost and carries
the (P, N) state in VMEM scratch — constant on-chip footprint in S.
Validated in interpret mode against ``ref.ssd_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    c_id = pl.program_id(1)

    @pl.when(c_id == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[:, 0, :].astype(jnp.float32)       # (L, P)
    dt = dt_ref[:, 0].astype(jnp.float32)        # (L,)
    A = a_ref[0].astype(jnp.float32)             # scalar decay (negative)
    B = b_ref[...].astype(jnp.float32)           # (L, N)
    C = c_ref[...].astype(jnp.float32)           # (L, N)

    s = jnp.cumsum(dt * A)                       # (L,) log decay to t (incl.)
    seg = s[:, None] - s[None, :]                # s_t - s_u
    L_idx = jax.lax.iota(jnp.int32, chunk)
    causal = L_idx[:, None] >= L_idx[None, :]
    M = jnp.where(causal, jnp.exp(seg), 0.0)     # (L, L)

    h_in = state_ref[...]                        # (P, N)
    # inter-chunk contribution: exp(s_t) * C_t h_in
    y_inter = jnp.exp(s)[:, None] * jnp.dot(C, h_in.T,
                                            preferred_element_type=jnp.float32)
    # intra-chunk: (M ⊙ (C B^T)) @ (dt ⊙ x)
    CB = jnp.dot(C, B.T, preferred_element_type=jnp.float32)   # (L, L)
    y_intra = jnp.dot(M * CB, dt[:, None] * x,
                      preferred_element_type=jnp.float32)       # (L, P)
    y_ref[:, 0, :] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update: h_out = exp(s_L) h_in + Σ_u exp(s_L - s_u) dt_u x_u B_u^T
    w = jnp.exp(s[-1] - s) * dt                   # (L,)
    h_new = jnp.exp(s[-1]) * h_in + jnp.dot(
        (w[:, None] * x).T, B, preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = h_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array | None = None, *, chunk: int = 64,
             interpret: bool | None = None) -> jax.Array:
    """x: (S, H, P), dt: (S, H), A: (H,), B/C: (S, N) -> (S, H, P)."""
    S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must divide chunk={chunk}")
    grid = (H, S // chunk)

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, P), lambda h, c: (c, h, 0)),
            pl.BlockSpec((chunk, 1), lambda h, c: (c, h)),
            pl.BlockSpec((1,), lambda h, c: (h,)),
            pl.BlockSpec((chunk, N), lambda h, c: (c, 0)),
            pl.BlockSpec((chunk, N), lambda h, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, 1, P), lambda h, c: (c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=pallas_interpret(interpret),
    )(x, dt, A, B, C)
    if D is not None:
        y = y + (D[None, :, None] * x.astype(jnp.float32)).astype(y.dtype)
    return y


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array | None = None,
                chunk: int = 64) -> jax.Array:
    """Same chunked algorithm in pure jnp (XLA path used by the mamba2
    model on any backend; the dry-run/roofline path).  x: (S, H, P)."""
    S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    xc = x.reshape(nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(nc, chunk, H).astype(jnp.float32)
    Bc = B.reshape(nc, chunk, N).astype(jnp.float32)
    Cc = C.reshape(nc, chunk, N).astype(jnp.float32)
    A32 = A.astype(jnp.float32)

    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]

    def step(h, inputs):                        # h: (H, P, N)
        xk, dtk, Bk, Ck = inputs                # (L,H,P), (L,H), (L,N), (L,N)
        s = jnp.cumsum(dtk * A32[None, :], axis=0)          # (L, H)
        M = jnp.where(causal[:, :, None], jnp.exp(s[:, None] - s[None, :]), 0.0)
        CB = Ck @ Bk.T                                        # (L, L)
        y_intra = jnp.einsum("tuh,tu,uhp->thp", M, CB, dtk[:, :, None] * xk)
        y_inter = jnp.exp(s)[:, :, None] * jnp.einsum("tn,hpn->thp", Ck, h)
        w = jnp.exp(s[-1][None, :] - s) * dtk                 # (L, H)
        h_new = (jnp.exp(s[-1])[:, None, None] * h
                 + jnp.einsum("uhp,un->hpn", w[:, :, None] * xk, Bk))
        return h_new, y_intra + y_inter

    h0 = jnp.zeros((H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (xc, dtc, Bc, Cc))
    y = ys.reshape(S, H, P)
    if D is not None:
        y = y + D[None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)
