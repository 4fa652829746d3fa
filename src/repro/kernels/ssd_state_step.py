"""One decode step of the Mamba2 SSD state, in place in a stack of layers.

Per sequence and head the state ``h`` (headdim P x d_state N, float32)
steps as ``h = exp(dt A) h + (dt x) B^T`` and is read out as
``y = h C``.  The serving engine keeps every layer's state in one
carried stack, so the kernel reads layer ``layer`` of each sequence
once, writes the new state back into the same buffer
(``input_output_aliases``) and returns ``y`` from the same read: one
read and one write of the state per layer and step.

Layout: the state is stored state-major, ``(..., N, H*P)``: the heads'
P-vectors lie along the lanes, the state dimension along the sublanes.
Then every per-head coefficient is a lane vector (``decay`` repeated over
each head's P lanes, ``dt x``) and broadcasts down the sublanes, ``B``
and ``C`` are the only columns (one each per sequence), and the readout
sums down the sublanes into a lane-dense ``y``, all in float32 on the
VPU (Mosaic's float32 matmul is one bfloat16 pass).

The layer index is prefetched into SMEM as a scalar operand and picks
the layer in the state's index map.  A grid step takes several
sequences, sized so that a block of state is a few MiB and the step's
overhead stays under its DMA.  ``ssd_state_step_ref`` is the same step
in plain jnp, the model's XLA path and the kernel's oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_interpret

_BLOCK_BYTES = 8 << 20          # state bytes a grid step reads, at most


def _kernel(layer_ref, dec_ref, dtx_ref, b_ref, c_ref, h_ref, y_ref, o_ref,
            *, chunk: int):
    del layer_ref                                     # read by index maps
    gb, bb, n, width = h_ref.shape
    for r in range(gb * bb):
        g, b = divmod(r, bb)
        row = pl.ds(r, 1)
        bcol = jnp.broadcast_to(b_ref[row, :].reshape(n, 1), (n, chunk))
        ccol = jnp.broadcast_to(c_ref[row, :].reshape(n, 1), (n, chunk))
        for c0 in range(0, width, chunk):
            lanes = pl.ds(c0, chunk)
            h = h_ref[g, b, :, lanes] * dec_ref[row, lanes] + \
                bcol * dtx_ref[row, lanes]
            o_ref[g, b, :, lanes] = h
            y_ref[row, lanes] = jnp.sum(h * ccol, axis=0, keepdims=True)


def _rows_per_block(rows: int, batch: int, row_bytes: int) -> int:
    """Sequences a grid step takes: a divisor of ``rows`` that keeps the
    (rows, ...) operands' blocks on the TPU's tiling (a multiple of 8, or
    all of them) and spans whole groups or whole parts of one group of
    ``batch``; the largest whose state fits ``_BLOCK_BYTES``."""
    ok = [d for d in range(1, rows + 1)
          if rows % d == 0 and (d % 8 == 0 or d == rows)
          and (batch % d == 0 or d % batch == 0)]
    fit = [d for d in ok if d * row_bytes <= _BLOCK_BYTES]
    return max(fit) if fit else min(ok)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_groups(state, layer, decay, dtx, bmat, cmat, *,
                 interpret: bool | None = None):
    """state: (G, L, B, N, W) float32, W = H*P; layer: int scalar;
    decay, dtx: (G, B, W) float32; bmat, cmat: (G, B, N) float32.
    Returns (y (G, B, W), the stack with layer ``layer`` stepped)."""
    G, _, B, N, W = state.shape
    rows = G * B
    rb = _rows_per_block(rows, B, N * W * 4)
    gb, bb = max(rb // B, 1), min(rb, B)
    nb = B // bb                                      # blocks per group
    chunk = 128 if W % 128 == 0 else W
    flat = lambda a: a.reshape(rows, a.shape[-1])
    vec = lambda w: pl.BlockSpec((rb, w), lambda i, l: (i, 0))
    st = pl.BlockSpec((gb, None, bb, N, W),
                      lambda i, l: (i // nb, l[0], i % nb, 0, 0))
    block = rb * N * W * 4
    small = rb * (3 * W + 2 * N) * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(rows // rb,),
        in_specs=[vec(W), vec(W), vec(N), vec(N), st],
        out_specs=[vec(W), st])
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * (2 * block + small) + (16 << 20)),
        interpret=pallas_interpret(interpret),
        name="ssd_state_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), flat(decay), flat(dtx),
      flat(bmat), flat(cmat), state)
    return y.reshape(G, B, W), state


@jax.custom_batching.custom_vmap
def _step_groups_vmappable(state, layer, decay, dtx, bmat, cmat):
    return _step_groups(state, layer, decay, dtx, bmat, cmat)


@_step_groups_vmappable.def_vmap
def _vmap_fold_groups(axis_size, in_batched, state, layer, decay, dtx, bmat,
                      cmat):
    """One kernel call for the whole mapped axis, folded into the group
    axis (a reshape of the leading axes, so the stack is not copied),
    with unmapped operands broadcast to it.  Pallas's own rule would loop
    over the mapped axis, one call per element on a copy of its slice."""
    if in_batched[1]:
        raise NotImplementedError(
            "ssd_state_step: the layer index must not be mapped")

    def fold(a, batched):
        if not batched:
            a = jnp.broadcast_to(a, (axis_size,) + a.shape)
        return a.reshape((-1,) + a.shape[2:])

    args = [fold(a, b) for a, b in zip((state, decay, dtx, bmat, cmat),
                                       in_batched[:1] + in_batched[2:])]
    y, new = _step_groups_vmappable(args[0], layer, *args[1:])
    unfold = lambda a: a.reshape((axis_size, -1) + a.shape[1:])
    return (unfold(y), unfold(new)), (True, True)


def ssd_state_step(state, layer, decay, dtx, bmat, cmat):
    """state: (L, B, N, H*P) float32, the stack of every layer's state;
    layer: int scalar; decay: (B, H*P), ``exp(dt A)`` repeated over each
    head's P lanes; dtx: (B, H*P), ``dt x``; bmat, cmat: (B, N).  All
    float32.  Returns (y (B, H*P), the stack with layer ``layer``
    stepped in place).  Under ``vmap`` (the serving engine's slots) one
    call steps every element of the mapped axis."""
    y, new = _step_groups_vmappable(state[None], layer, decay[None],
                                    dtx[None], bmat[None], cmat[None])
    return y[0], new[0]


def ssd_state_step_ref(state, layer, decay, dtx, bmat, cmat):
    """:func:`ssd_state_step` in plain jnp: the layer's state sliced out,
    stepped, and written back with ``dynamic_update_slice``."""
    h = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    h = h * decay[:, None, :] + bmat[:, :, None] * dtx[:, None, :]
    y = jnp.sum(h * cmat[:, :, None], axis=1)
    return y, jax.lax.dynamic_update_index_in_dim(state, h, layer, 0)
