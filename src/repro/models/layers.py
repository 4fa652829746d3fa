"""Shared model primitives: params maker, norms, rope, attention, MLP.

Every ``init_*`` function takes a ``Maker``; the same code path produces
real arrays (mode="init"), ShapeDtypeStructs (mode="shape", used by the
dry-run so no memory is ever allocated), or logical-axes strings
(mode="axes", consumed by the sharding resolver).  One definition, three
interpretations — no drift between init, sharding and checkpoint layouts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (current_mesh, current_rules,
                                        pspec_for, shard)

Params = Dict[str, Any]


@dataclasses.dataclass
class Maker:
    mode: str                       # "init" | "shape" | "axes"
    key: Optional[jax.Array] = None
    dtype: Any = jnp.float32

    def __call__(self, shape: Tuple[int, ...], axes: str,
                 init: str = "normal", scale: float = 0.02):
        if self.mode == "axes":
            return axes
        if self.mode == "shape":
            return jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        assert self.key is not None
        self.key, sub = jax.random.split(self.key)
        if init == "zeros":
            return jnp.zeros(shape, self.dtype)
        if init == "ones":
            return jnp.ones(shape, self.dtype)
        if init == "normal":
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            s = min(scale, (1.0 / fan_in) ** 0.5) if len(shape) > 1 else scale
            return (jax.random.normal(sub, shape) * s).astype(self.dtype)
        raise ValueError(init)


# --------------------------------------------------------------------------
# norms / rope / positions
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA, windows, caches)
# --------------------------------------------------------------------------


def init_attention(cfg, mk: Maker) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    p = {
        "norm": mk((d,), "embed", init="zeros"),
        "wq": mk((d, H * hd), "fsdp heads"),
        "wk": mk((d, KV * hd), "fsdp kv_heads"),
        "wv": mk((d, KV * hd), "fsdp kv_heads"),
        "wo": mk((H * hd, d), "heads fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = mk((H * hd,), "heads", init="zeros")
        p["bk"] = mk((KV * hd,), "kv_heads", init="zeros")
        p["bv"] = mk((KV * hd,), "kv_heads", init="zeros")
    return p


def init_cross_attention(cfg, mk: Maker) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H = cfg.num_heads
    ed = cfg.encoder.d_model or d
    return {
        "norm": mk((d,), "embed", init="zeros"),
        "wq": mk((d, H * hd), "fsdp heads"),
        "wk": mk((ed, H * hd), "fsdp heads"),
        "wv": mk((ed, H * hd), "fsdp heads"),
        "wo": mk((H * hd, d), "heads fsdp"),
    }


def _mask(qpos: jax.Array, kpos: jax.Array, kv_len: Optional[jax.Array],
          causal: bool, window) -> jax.Array:
    """(..., Sq, Sk) boolean mask.  ``window`` may be a traced scalar
    (per-layer local window; big value = global)."""
    m = jnp.ones(qpos.shape[:-1] + (qpos.shape[-1], kpos.shape[-1]), bool)
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    if causal:
        m &= k <= q
    if window is not None:
        m &= k > q - window
    if kv_len is not None:
        m &= k < (kv_len[..., None, None] if kv_len.ndim else kv_len)
    return m


def attention_math(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: jax.Array, backend: str = "xla",
                   scale: Optional[float] = None) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); mask: (B, Sq, Sk) or
    broadcastable.  GQA via head grouping (no KV materialised repeat)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    scale = float(scale) if scale is not None else 1.0 / (hd ** 0.5)
    qg = q.reshape(B, Sq, KV, rep, hd).astype(jnp.float32)
    logits = jnp.einsum("bqgrh,bkgh->bgrqk", qg * scale,
                        k.astype(jnp.float32))
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgh->bqgrh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


# threshold above which attention switches to the blockwise (flash-style)
# XLA path: never materialise an (Sq, Sk) logits tensor past this size.
_DIRECT_LIMIT = 1 << 21

# Tunable execution options for the blockwise path (§Perf iteration C):
#   probs_dtype — dtype of the softmax weights entering the PV matmul.
#     Statistics (m, l) always stay f32; bf16 probs halve the dominant
#     HBM term of long-sequence attention at <1e-2 output error.
#   block_q/block_k — VMEM-tile analogue of the stagecc tile sizes.
ATTN_OPTIONS = {"probs_dtype": jnp.float32, "block_q": 512, "block_k": 1024}


def set_attention_options(probs_dtype=None, block_q=None, block_k=None):
    if probs_dtype is not None:
        ATTN_OPTIONS["probs_dtype"] = (
            jnp.bfloat16 if str(probs_dtype) in ("bf16", "bfloat16")
            else jnp.float32)
    if block_q is not None:
        ATTN_OPTIONS["block_q"] = int(block_q)
    if block_k is not None:
        ATTN_OPTIONS["block_k"] = int(block_k)


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   qpos: jax.Array, kpos: jax.Array,
                   valid: Optional[jax.Array], causal: bool, window,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   scale: Optional[float] = None) -> jax.Array:
    """Position-based attention that never builds a full (Sq, Sk) mask.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); qpos: (B, Sq); kpos: (B, Sk);
    ``valid``: scalar count of valid cache entries (decode) or None;
    ``window`` may be a traced scalar (per-layer local window).

    Small problems take the direct path; large ones run a blockwise
    online-softmax (the flash algorithm expressed in XLA: a lax.scan over
    KV blocks nested in a scan over Q blocks), keeping live memory
    O(block_q x block_k) per head — this is what makes the 32k/500k
    cells compile with sane footprints on the dry-run, and mirrors the
    pallas kernel used on real TPU.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = float(scale) if scale is not None else 1.0 / (hd ** 0.5)
    block_q = block_q or ATTN_OPTIONS["block_q"]
    block_k = block_k or ATTN_OPTIONS["block_k"]
    pdt = ATTN_OPTIONS["probs_dtype"]

    def mask_for(qp, kp):                       # (B, sq) x (B, sk) -> bool
        m = jnp.ones((B, qp.shape[1], kp.shape[1]), bool)
        kk = kp[:, None, :]
        qq = qp[:, :, None]
        if causal:
            m &= kk <= qq
        if window is not None:
            m &= kk > qq - window
        if valid is not None:
            m &= kk < valid
        return m

    if Sq * Sk <= _DIRECT_LIMIT or Sq % min(block_q, Sq) or \
            Sk % min(block_k, Sk):
        return attention_math(q, k, v, mask_for(qpos, kpos), scale=scale)

    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = Sq // bq, Sk // bk
    qg = q.reshape(B, nq, bq, KV, rep, hd)
    qpos_b = qpos.reshape(B, nq, bq)
    kb = k.reshape(B, nk, bk, KV, hd)
    vb = v.reshape(B, nk, bk, KV, v.shape[-1])
    kpos_b = kpos.reshape(B, nk, bk)

    def q_step(_, xs):
        qblk, qp = xs                            # (B,bq,KV,rep,hd), (B,bq)
        qblk = qblk.astype(jnp.float32) * scale

        def kv_step(carry, kxs):
            m_run, l_run, acc = carry
            kblk, vblk, kp = kxs
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qblk,
                           kblk.astype(jnp.float32))
            msk = mask_for(qp, kp)[:, None, None]
            s = jnp.where(msk, s, -1e30)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", p.astype(pdt), vblk.astype(pdt),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, rep, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, rep, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, rep, bq, v.shape[-1]), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kpos_b.transpose(1, 0, 2)))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return None, out                        # (B,KV,rep,bq,hv)

    _, outs = jax.lax.scan(q_step, None,
                           (qg.transpose(1, 0, 2, 3, 4, 5),
                            qpos_b.transpose(1, 0, 2)))
    # outs: (nq, B, KV, rep, bq, hv) -> (B, Sq, H, hv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, v.shape[-1])
    return out.astype(q.dtype)


def apply_attention(p: Params, x: jax.Array, cfg, positions: jax.Array,
                    window=None, cache: Optional[Params] = None,
                    kv_len: Optional[jax.Array] = None,
                    backend: str = "xla",
                    causal: bool = True,
                    layer: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Optional[Params]]:
    """Pre-norm GQA attention block with optional KV cache.

    Training/prefill: x is (B, S, d), cache None/fresh. Decode: x is
    (B, 1, d) and ``cache`` holds (B, KV, Smax, hd) buffers, head-major
    as the decode kernel reads them, with ``kv_len`` tokens valid before
    this call.  With ``layer``, ``cache`` holds the (L, B, KV, Smax, hd)
    stacks of a layer scan: the new rows are written at ``layer`` where
    the stack lies, and the returned cache is the whole stack.
    """
    B, S, d = x.shape
    hd, H, KV = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", h, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", h, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", h, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q.reshape(B, S, H, hd), "batch", None, "heads", None)
    k = shard(k.reshape(B, S, KV, hd), "batch", None, "kv_heads", None)
    v = shard(v.reshape(B, S, KV, hd), "batch", None, "kv_heads", None)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        # insert at kv_len (scalar; same for all batch rows)
        start = kv_len if kv_len is not None else jnp.int32(0)
        new_cache = {n: _append_rows(cache[n], rows, start, layer)
                     for n, rows in (("k", k), ("v", v))}
        k, v = (new_cache[n] if layer is None else
                jax.lax.dynamic_index_in_dim(new_cache[n], layer, 0,
                                             keepdims=False)
                for n in ("k", "v"))                 # (B, KV, Smax, hd)
        valid = start + S
    else:
        valid = None

    if (backend == "pallas" and cache is not None and S == 1
            and window is None):
        # serving fast path: the pallas decode kernel attends the cache
        # with VMEM-resident statistics (kernels/decode_attention.py)
        from repro.kernels.decode_attention import \
            decode_attention_vmappable
        rep = H // KV
        qd = q.reshape(B, KV, rep, hd)
        kernel = decode_attention_vmappable
        mesh = current_mesh()
        if mesh is not None:
            # a Mosaic kernel is not partitioned automatically: run it on
            # each device's shard of the batch and kv-head axes
            spec = pspec_for(("batch", "kv_heads"), (B, KV), mesh,
                             current_rules())
            vspec = P(*spec[:1])
            kernel = jax.shard_map(decode_attention_vmappable, mesh=mesh,
                                   in_specs=(spec, spec, spec, vspec),
                                   out_specs=spec, check_vma=False)
        out = kernel(qd, k, v, jnp.broadcast_to(jnp.asarray(valid), (B,)))
        out = out.reshape(B, S, H, hd)
    else:
        if cache is not None:
            k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
            kpos = jnp.arange(k.shape[1])[None, :]
        else:
            kpos = positions
        out = attention_core(q, k, v, positions,
                             jnp.broadcast_to(kpos, (B, k.shape[1])),
                             None if valid is None else jnp.asarray(valid),
                             causal=causal, window=window)
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"].reshape(H, hd, d))
    return x + shard(out, "batch", None, None), new_cache


def _append_rows(buf: jax.Array, rows: jax.Array, start,
                 layer: Optional[jax.Array]) -> jax.Array:
    """Write ``rows`` (B, S, KV, hd) into the head-major cache ``buf`` at
    row ``start``: (B, KV, Smax, hd), or at ``layer`` of an
    (L, B, KV, Smax, hd) stack."""
    rows = jnp.swapaxes(rows, 1, 2).astype(buf.dtype)
    zero = jnp.zeros((), jnp.int32)
    at = (zero, zero, jnp.asarray(start, jnp.int32), zero)
    if layer is not None:
        rows, at = rows[None], (jnp.asarray(layer, jnp.int32),) + at
    return jax.lax.dynamic_update_slice(buf, rows, at)


def apply_cross_attention(p: Params, x: jax.Array, cfg,
                          enc_kv: Tuple[jax.Array, jax.Array]
                          ) -> jax.Array:
    """Decoder cross-attention; enc_kv = (k, v): (B, Senc, H, hd)."""
    B, S, d = x.shape
    hd, H = cfg.resolved_head_dim, cfg.num_heads
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", h, p["wq"]).reshape(B, S, H, hd)
    k, v = enc_kv
    m = jnp.ones((B, S, k.shape[1]), bool)
    out = attention_math(q, k, v, m)
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"].reshape(H, hd, d))
    return x + out


def cross_kv(p: Params, cfg, enc_out: jax.Array):
    B, Se, ed = enc_out.shape
    hd, H = cfg.resolved_head_dim, cfg.num_heads
    k = jnp.einsum("bsd,dq->bsq", enc_out, p["wk"]).reshape(B, Se, H, hd)
    v = jnp.einsum("bsd,dq->bsq", enc_out, p["wv"]).reshape(B, Se, H, hd)
    return k, v


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def init_mlp(cfg, mk: Maker, ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = ff or cfg.d_ff
    p = {"norm": mk((d,), "embed", init="zeros")}
    if cfg.mlp.startswith("gated"):
        p["w_gate"] = mk((d, ff), "fsdp ff")
        p["w_up"] = mk((d, ff), "fsdp ff")
        p["w_down"] = mk((ff, d), "ff fsdp")
    else:
        p["w_up"] = mk((d, ff), "fsdp ff")
        p["w_down"] = mk((ff, d), "ff fsdp")
    return p


def apply_mlp(p: Params, x: jax.Array, cfg) -> jax.Array:
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    if cfg.mlp.startswith("gated"):
        act = jax.nn.silu if cfg.mlp == "gated_silu" else jax.nn.gelu
        g = act(jnp.einsum("bsd,df->bsf", h, p["w_gate"]))
        u = jnp.einsum("bsd,df->bsf", h, p["w_up"])
        hidden = shard(g * u, "batch", None, "ff")
    else:
        hidden = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, p["w_up"]))
        hidden = shard(hidden, "batch", None, "ff")
    out = jnp.einsum("bsf,fd->bsd", hidden, p["w_down"])
    return x + shard(out, "batch", None, None)
