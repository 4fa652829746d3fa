"""Architecture-generic transformer stack with pattern-group layer scan.

All ten assigned architectures compile through this module.  Layers are
grouped by the config's cyclic ``pattern``; the repeated groups run under
``jax.lax.scan`` (stacked params — O(1) HLO in depth, essential both for
compile time on huge configs and for remat ergonomics), with any
non-conforming prefix (e.g. deepseek's leading dense layer) or suffix
(recurrentgemma's trailing recurrent pair) unrolled around the scan.

Per-layer local-attention windows (gemma3's 5 local : 1 global) are
threaded through the scan as data, so mixed local/global stacks still
compile as one homogeneous scan without HLO branch duplication.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models.layers import Maker, Params

GLOBAL_WINDOW = 1 << 30          # "no window" sentinel carried through scans


# --------------------------------------------------------------------------
# layer kinds
# --------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> List[str]:
    kinds = cfg.layer_kinds()
    if cfg.moe is not None:
        for i in range(min(cfg.moe.first_dense_layers, len(kinds))):
            kinds[i] = "dense_moe"
    if cfg.encoder is not None:
        kinds = ["xdec"] * cfg.num_layers
    return kinds


def _init_layer(cfg: ModelConfig, kind: str, mk: Maker) -> Params:
    if kind == "attn":
        return {"attn": L.init_attention(cfg, mk), "mlp": L.init_mlp(cfg, mk)}
    if kind == "dense_moe":
        att = (mla_mod.init_mla(cfg, mk) if cfg.mla is not None
               else L.init_attention(cfg, mk))
        return {"attn": att,
                "mlp": L.init_mlp(cfg, mk, ff=cfg.moe.dense_ff or cfg.d_ff)}
    if kind == "moe":
        att = (mla_mod.init_mla(cfg, mk) if cfg.mla is not None
               else L.init_attention(cfg, mk))
        return {"attn": att, "moe": moe_mod.init_moe(cfg, mk)}
    if kind == "rglru":
        return {"rec": rec_mod.init_rglru(cfg, mk),
                "mlp": L.init_mlp(cfg, mk)}
    if kind == "ssd":
        return {"ssd": rec_mod.init_ssd(cfg, mk)}
    if kind == "xdec":
        return {"attn": L.init_attention(cfg, mk),
                "cross": L.init_cross_attention(cfg, mk),
                "mlp": L.init_mlp(cfg, mk)}
    raise ValueError(f"unknown layer kind {kind!r}")


def _apply_layer(kind: str, p: Params, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array, window, cache, kv_len,
                 backend: str, enc_kv=None, layer=None):
    """Returns (x, new_cache, aux_loss).  ``layer``: the scan index at
    which the carried stacks in the cache (``cache["attn"]``'s appended
    K/V, ``cache["ssd"]``'s state and window) are written and read (see
    :func:`_split_carried`)."""
    cache = cache if cache else None
    aux = jnp.float32(0.0)
    if kind in ("attn", "dense_moe", "moe", "xdec"):
        attn_cache = cache.get("attn") if cache else None
        if cfg.mla is not None and kind in ("moe", "dense_moe"):
            x, nc = mla_mod.apply_mla(p["attn"], x, cfg, positions,
                                      cache=attn_cache, kv_len=kv_len)
        else:
            x, nc = L.apply_attention(p["attn"], x, cfg, positions,
                                      window=window, cache=attn_cache,
                                      kv_len=kv_len, backend=backend,
                                      layer=layer)
        new_cache = {"attn": nc} if nc is not None else None
        if kind == "xdec":
            if enc_kv is not None:           # encoder ran this call (train/prefill)
                ekv = enc_kv(p["cross"])     # callable: builds k/v from enc
            else:                            # decode: use cached cross-KV
                ekv = (cache["xk"], cache["xv"])
            x = L.apply_cross_attention(p["cross"], x, cfg, ekv)
            if new_cache is not None:
                new_cache["xk"], new_cache["xv"] = ekv
        if kind == "moe":
            x, aux = moe_mod.apply_moe(p["moe"], x, cfg)
        else:
            x = L.apply_mlp(p["mlp"], x, cfg)
        return x, new_cache, aux
    if kind == "rglru":
        x, nc = rec_mod.apply_rglru(p["rec"], x, cfg,
                                    cache.get("rec") if cache else None)
        x = L.apply_mlp(p["mlp"], x, cfg)
        return x, ({"rec": nc} if nc is not None else None), aux
    if kind == "ssd":
        x, nc = rec_mod.apply_ssd(p["ssd"], x, cfg,
                                  cache.get("ssd") if cache else None,
                                  backend=backend, layer=layer)
        return x, ({"ssd": nc} if nc is not None else None), aux
    raise ValueError(kind)


def _appends_kv(cfg: ModelConfig, kind: str) -> bool:
    """Whether a layer of ``kind`` keeps an attention K/V cache that each
    call appends rows to (and does not rewrite whole)."""
    return kind in ("attn", "xdec") or (kind in ("moe", "dense_moe")
                                        and cfg.mla is None)


def _carried_key(cfg: ModelConfig, kind: str) -> Optional[str]:
    """The part of a layer's cache that a call updates in place and so
    rides the layer scan's carry: appended attention K/V ("attn"), or the
    SSD state and convolution window ("ssd"); None for the rest."""
    if _appends_kv(cfg, kind):
        return "attn"
    return "ssd" if kind == "ssd" else None


def _layer_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      dtype) -> Dict[str, Any]:
    hd, KV = cfg.resolved_head_dim, cfg.num_kv_heads
    if _appends_kv(cfg, kind):
        # head-major, the layout the decode kernel reads
        spec = {"attn": {
            "k": jax.ShapeDtypeStruct((batch, KV, max_len, hd), dtype),
            "v": jax.ShapeDtypeStruct((batch, KV, max_len, hd), dtype)}}
        if kind == "xdec":
            e = cfg.encoder
            H = cfg.num_heads
            spec["xk"] = jax.ShapeDtypeStruct((batch, e.context, H, hd), dtype)
            spec["xv"] = jax.ShapeDtypeStruct((batch, e.context, H, hd), dtype)
        return spec
    if kind in ("moe", "dense_moe"):         # MLA compressed cache
        a = cfg.mla
        return {"attn": {
            "ckv": jax.ShapeDtypeStruct((batch, max_len, a.kv_lora), dtype),
            "kr": jax.ShapeDtypeStruct((batch, max_len, a.qk_rope_dim), dtype)}}
    if kind == "rglru":
        return {"rec": rec_mod.rglru_cache_spec(cfg, batch, dtype)}
    if kind == "ssd":
        return {"ssd": rec_mod.ssd_cache_spec(cfg, batch, dtype)}
    raise ValueError(kind)


_CACHE_AXES = {"k": "batch kv_heads kv_seq -", "v": "batch kv_heads kv_seq -",
               "ckv": "batch kv_seq -", "kr": "batch kv_seq -",
               "xk": "batch - heads -", "xv": "batch - heads -",
               "h": "batch ff", "conv": "batch - ff",
               "state": "batch - heads"}


def _cache_axes(spec) -> Any:
    def walk(d):
        return {k: (walk(v) if isinstance(v, dict) else _CACHE_AXES[k])
                for k, v in d.items()}
    return walk(spec)


# --------------------------------------------------------------------------
# layer grouping: prefix / scanned pattern groups / suffix
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackPlan:
    prefix: Tuple[int, ...]          # layer indices unrolled before the scan
    pattern: Tuple[str, ...]         # kinds of one scanned group
    groups: int                      # number of scanned groups
    suffix: Tuple[int, ...]          # layer indices unrolled after


def stack_plan(cfg: ModelConfig) -> StackPlan:
    kinds = layer_kinds(cfg)
    n = len(kinds)
    # prefix = leading layers not matching the cyclic pattern of the rest
    start = 0
    if cfg.moe is not None:
        start = min(cfg.moe.first_dense_layers, n)
    period_kinds = tuple(kinds[start:start + _period(cfg)])
    period = len(period_kinds)
    groups = (n - start) // period if period else 0
    used = start + groups * period
    return StackPlan(prefix=tuple(range(start)), pattern=period_kinds,
                     groups=groups, suffix=tuple(range(used, n)))


def _period(cfg: ModelConfig) -> int:
    if cfg.encoder is not None:
        return 1
    return len(cfg.pattern)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, mode: str = "shape",
                key: Optional[jax.Array] = None, dtype=jnp.float32,
                max_seq: int = 0) -> Params:
    """mode: "init" (arrays) | "shape" (ShapeDtypeStructs) | "axes"."""
    plan = stack_plan(cfg)
    kinds = layer_kinds(cfg)
    windows = cfg.layer_windows()
    if key is None and mode == "init":
        key = jax.random.PRNGKey(0)

    def mk_for(k):
        return Maker(mode, k, dtype)

    def split(k):
        if mode != "init":
            return None, None
        return jax.random.split(k)

    p: Params = {}
    key, sub = split(key) if mode == "init" else (None, None)
    p["embed"] = mk_for(sub)((cfg.padded_vocab, cfg.d_model), "vocab fsdp")
    if not cfg.rope_theta:
        pos_len = max(max_seq, 2048)
        key, sub = split(key) if mode == "init" else (None, None)
        p["pos_embed"] = mk_for(sub)((pos_len, cfg.d_model), "- fsdp")
    # prefix / suffix layers, unrolled
    for name, idxs in (("prefix", plan.prefix), ("suffix", plan.suffix)):
        if idxs:
            sub_p = {}
            for i in idxs:
                key, sub = split(key) if mode == "init" else (None, None)
                sub_p[str(i)] = _init_layer(cfg, kinds[i], mk_for(sub))
            p[name] = sub_p
    # scanned groups: stacked along a leading axis
    if plan.groups:
        scan_p = {}
        for pos, kind in enumerate(plan.pattern):
            if mode == "axes":
                one = _init_layer(cfg, kind, Maker("axes"))
                scan_p[f"pos{pos}"] = jax.tree.map(
                    lambda s: ("- " + s) if s else "-", one)
            elif mode == "shape":
                one = _init_layer(cfg, kind, Maker("shape", dtype=dtype))
                scan_p[f"pos{pos}"] = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        (plan.groups,) + s.shape, s.dtype), one)
            else:
                key, sub = split(key)
                keys = jax.random.split(sub, plan.groups)
                scan_p[f"pos{pos}"] = jax.vmap(
                    lambda kk: _init_layer(cfg, kind, Maker("init", kk,
                                                            dtype)))(keys)
        p["scan"] = scan_p
    key, sub = split(key) if mode == "init" else (None, None)
    p["final_norm"] = mk_for(sub)((cfg.d_model,), "embed", init="zeros")
    if not cfg.tie_embeddings:
        key, sub = split(key) if mode == "init" else (None, None)
        p["lm_head"] = mk_for(sub)((cfg.d_model, cfg.padded_vocab), "fsdp vocab")
    if cfg.encoder is not None:
        p["encoder"] = _init_encoder(cfg, mode, key, dtype)
    return p


def _init_encoder(cfg: ModelConfig, mode, key, dtype) -> Params:
    e = cfg.encoder
    ed = e.d_model or cfg.d_model
    enc_cfg = dataclasses.replace(
        cfg, d_model=ed, num_layers=e.num_layers, pattern=("attn",),
        rope_theta=0.0, moe=None, mla=None, encoder=None)
    p: Params = {}
    if mode == "init":
        key, k1, k2, k3 = jax.random.split(key, 4)
    else:
        k1 = k2 = k3 = None
    p["pos_embed"] = Maker(mode, k1, dtype)((e.context, ed), "- fsdp")
    if mode == "axes":
        one = _init_layer(enc_cfg, "attn", Maker("axes"))
        p["scan"] = jax.tree.map(lambda s: ("- " + s) if s else "-", one)
    elif mode == "shape":
        one = _init_layer(enc_cfg, "attn", Maker("shape", dtype=dtype))
        p["scan"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((e.num_layers,) + s.shape,
                                           s.dtype), one)
    else:
        keys = jax.random.split(k2, e.num_layers)
        p["scan"] = jax.vmap(
            lambda kk: _init_layer(enc_cfg, "attn", Maker("init", kk,
                                                          dtype)))(keys)
    p["final_norm"] = Maker(mode, k3, dtype)((ed,), "embed", init="zeros")
    return p


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _split_carried(cfg: ModelConfig, plan: StackPlan, scan_cache):
    """Split the scanned groups' caches, per pattern position, into
    (carried, rest).

    ``carried``: the stacks of the position's :func:`_carried_key` (the
    appended attention K/V, or the SSD state and convolution window), or
    None where it has none.  They ride the layer scan's carry: each layer
    writes its update where the stack lies and reads its own layer of it,
    so a decode step copies no stack in or out.  ``rest``: every other
    leaf (RG-LRU states, MLA's compressed cache, cross-attention K/V),
    sliced per layer as the scan's ``xs`` and stacked back as its
    ``ys``."""
    keys = [_carried_key(cfg, kind) if c else None
            for kind, c in zip(plan.pattern, scan_cache)]
    carried = tuple(None if k is None else c[k]
                    for k, c in zip(keys, scan_cache))
    rest = tuple({n: v for n, v in c.items() if n != k}
                 for k, c in zip(keys, scan_cache))
    return carried, rest


def _window_arrays(cfg: ModelConfig, plan: StackPlan
                   ) -> Tuple[Optional[jax.Array], ...]:
    """Per pattern position, the scanned windows of its layers, or None
    where every one of them is global (so the layer sees ``window=None``
    and keeps the paths that need a static global window, such as the
    pallas decode kernel)."""
    windows = cfg.layer_windows()
    out = []
    start = len(plan.prefix)
    period = len(plan.pattern)
    for pos in range(period):
        vals = [windows[start + g * period + pos] for g in range(plan.groups)]
        out.append(None if all(w is None for w in vals) else
                   jnp.asarray([GLOBAL_WINDOW if w is None else w
                                for w in vals], jnp.int32))
    return tuple(out)


def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    e = cfg.encoder
    ed = e.d_model or cfg.d_model
    enc_cfg = dataclasses.replace(
        cfg, d_model=ed, num_layers=e.num_layers, pattern=("attn",),
        rope_theta=0.0, moe=None, mla=None, encoder=None)
    x = frames + params["encoder"]["pos_embed"][None, :frames.shape[1]]
    positions = jnp.broadcast_to(jnp.arange(frames.shape[1])[None],
                                 frames.shape[:2])

    def body(x, p_slice):
        h, _ = L.apply_attention(p_slice["attn"], x, enc_cfg, positions,
                                 window=None, causal=False)
        h = L.apply_mlp(p_slice["mlp"], h, enc_cfg)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["scan"])
    return L.rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            extra_embeds: Optional[jax.Array] = None,
            cache: Optional[Params] = None,
            backend: str = "xla",
            remat: str = "none") -> Tuple[jax.Array, Optional[Params],
                                          jax.Array]:
    """tokens: (B, S) -> (logits (B, S, V), new_cache, aux_loss).

    cache=None: training forward.  cache given: prefill (S>1, fresh cache)
    or decode (S==1).  ``extra_embeds``: patch embeddings (pixtral) or
    frame embeddings (whisper encoder input).
    """
    plan = stack_plan(cfg)
    kinds = layer_kinds(cfg)
    B, S = tokens.shape
    kv_len = cache["len"] if cache is not None else None

    x = jnp.take(params["embed"], tokens, axis=0)
    x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    if cfg.frontend == "image_patches" and extra_embeds is not None:
        pl_ = min(extra_embeds.shape[1], S)
        x = jax.lax.dynamic_update_slice(
            x, extra_embeds[:, :pl_].astype(x.dtype), (0, 0, 0))
    start = kv_len if kv_len is not None else 0
    positions = start + jnp.arange(S)[None, :] + jnp.zeros((B, 1), jnp.int32)
    if not cfg.rope_theta:
        pos_tab = params["pos_embed"]
        x = x + jnp.take(pos_tab, jnp.minimum(positions, pos_tab.shape[0] - 1),
                         axis=0).astype(x.dtype)
    x = shard(x, "batch", None, None)

    enc_kv_fn = None
    if cfg.encoder is not None and extra_embeds is not None:
        enc_out = encode(params, cfg, extra_embeds)
        enc_kv_fn = lambda pc: L.cross_kv(pc, cfg, enc_out)

    aux_total = jnp.float32(0.0)
    new_cache: Dict[str, Any] = {} if cache is not None else None

    # ---- prefix layers (unrolled) ----
    for i in plan.prefix:
        c = cache["prefix"][str(i)] if cache is not None else None
        x, nc, aux = _apply_layer(kinds[i], params["prefix"][str(i)], x, cfg,
                                  positions, None, c, kv_len, backend,
                                  enc_kv_fn)
        aux_total += aux
        if cache is not None:
            new_cache.setdefault("prefix", {})[str(i)] = nc

    # ---- scanned pattern groups ----
    if plan.groups:
        windows = _window_arrays(cfg, plan)
        scan_params = tuple(params["scan"][f"pos{i}"]
                            for i in range(len(plan.pattern)))
        scan_cache = (tuple(cache["scan"][f"pos{i}"]
                            for i in range(len(plan.pattern)))
                      if cache is not None else
                      tuple({} for _ in plan.pattern))
        carried, scan_cache = _split_carried(cfg, plan, scan_cache)
        keys = [_carried_key(cfg, kind) for kind in plan.pattern]

        def body(carry, xs):
            x, cs = carry
            p_sl, c_sl, w_sl, g = xs
            ncs, new_cs = [], []
            aux_g = jnp.float32(0.0)
            for i, kind in enumerate(plan.pattern):
                c = c_sl[i] if cs[i] is None else {**c_sl[i], keys[i]: cs[i]}
                x, nc, aux = _apply_layer(kind, p_sl[i], x, cfg, positions,
                                          w_sl[i], c, kv_len, backend,
                                          enc_kv_fn,
                                          layer=None if cs[i] is None else g)
                nc = dict(nc) if nc is not None else {}
                new_cs.append(None if cs[i] is None else nc.pop(keys[i]))
                ncs.append(nc)
                aux_g = aux_g + aux
            return (x, tuple(new_cs)), (tuple(ncs), aux_g)

        if remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else None)
            body = jax.checkpoint(body, policy=policy,
                                  prevent_cse=False)
        (x, carried), (scan_new_cache, auxs) = jax.lax.scan(
            body, (x, carried),
            (scan_params, scan_cache, windows, jnp.arange(plan.groups)))
        aux_total += jnp.sum(auxs)
        if cache is not None:
            new_cache["scan"] = {
                f"pos{i}": (scan_new_cache[i] if c is None else
                            {**scan_new_cache[i], keys[i]: c})
                for i, c in enumerate(carried)}

    # ---- suffix layers (unrolled) ----
    for i in plan.suffix:
        c = cache["suffix"][str(i)] if cache is not None else None
        x, nc, aux = _apply_layer(kinds[i], params["suffix"][str(i)], x, cfg,
                                  positions, None, c, kv_len, backend,
                                  enc_kv_fn)
        aux_total += aux
        if cache is not None:
            new_cache.setdefault("suffix", {})[str(i)] = nc

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = shard(logits, "batch", None, "vocab")
    if cache is not None:
        new_cache["len"] = (kv_len + S).astype(jnp.int32)
        if cfg.encoder is not None:
            new_cache["enc_done"] = jnp.int32(1)
    return logits, new_cache, aux_total


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------


def cache_leaf_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(cache leaves that ride the layer scan's carry in place, all cache
    leaves but the lengths)."""
    plan = stack_plan(cfg)
    spec = cache_spec(cfg, 1, 1)
    scan = tuple(spec.get("scan", {}).get(f"pos{i}", {})
                 for i in range(len(plan.pattern)))
    carried, _ = _split_carried(cfg, plan, scan)
    per_layer = {n: v for n, v in spec.items() if n not in ("len", "enc_done")}
    return len(jax.tree.leaves(carried)), len(jax.tree.leaves(per_layer))


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32, mode: str = "shape") -> Params:
    """Cache pytree as ShapeDtypeStructs ("shape"), zeros ("init"), or
    logical-axes strings ("axes")."""
    plan = stack_plan(cfg)
    kinds = layer_kinds(cfg)
    spec: Dict[str, Any] = {}
    for name, idxs in (("prefix", plan.prefix), ("suffix", plan.suffix)):
        if idxs:
            spec[name] = {str(i): _layer_cache_spec(cfg, kinds[i], batch,
                                                    max_len, dtype)
                          for i in idxs}
    if plan.groups:
        sc = {}
        for pos, kind in enumerate(plan.pattern):
            one = _layer_cache_spec(cfg, kind, batch, max_len, dtype)
            sc[f"pos{pos}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((plan.groups,) + s.shape,
                                               s.dtype), one)
        spec["scan"] = sc
    spec["len"] = jax.ShapeDtypeStruct((), jnp.int32)
    if cfg.encoder is not None:
        spec["enc_done"] = jax.ShapeDtypeStruct((), jnp.int32)

    if mode == "shape":
        return spec
    if mode == "init":
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    if mode == "axes":
        def to_axes(path_leaf):
            return path_leaf
        def walk(d, under_scan=False):
            out = {}
            for k, v in d.items():
                if k == "len" or k == "enc_done":
                    out[k] = ""
                elif isinstance(v, dict):
                    out[k] = walk(v, under_scan or k == "scan")
                else:
                    ax = _CACHE_AXES[k]
                    out[k] = ("- " + ax) if under_scan else ax
            return out
        return walk(spec)
    raise ValueError(mode)
