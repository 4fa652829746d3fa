"""Model facade: build, init, apply, cache, and input specs per arch."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config
from repro.distributed.sharding import tree_shardings
from repro.launch.mesh import make_mesh
from repro.models import transformer as T

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Execution configuration orthogonal to the architecture."""
    param_dtype: str = "float32"
    activation_dtype: str = "float32"
    backend: str = "xla"               # xla | pallas
    remat: str = "none"                # none | full | dots
    max_seq: int = 4096                # position-table / cache upper bound
    cache_dtype: str = "float32"
    model_parallel: int = 1            # chips one replica's layers span
    prefill_chunk: int = 0             # serving: prompt tokens a step
                                       # prefills while slots decode
                                       # (0: whole prompts)


def _layout_mesh(model_parallel: int):
    """The ``(data=1, model=n)`` mesh over the first n devices, or None
    for one device."""
    if model_parallel <= 1:
        return None
    devices = jax.devices()
    if len(devices) < model_parallel:
        raise ValueError(f"model_parallel={model_parallel} needs as many "
                         f"devices; JAX found {len(devices)}")
    return make_mesh((1, model_parallel), ("data", "model"),
                     devices=devices[:model_parallel])


class Model:
    """Thin, stateless wrapper tying a ModelConfig to the generic stack.

    ``mesh`` is the run's layout: a ``(data=1, model=n)`` mesh where
    ``run.model_parallel`` is n > 1, else None (one device)."""

    def __init__(self, cfg: ModelConfig, run: RunConfig = RunConfig()):
        self.cfg = cfg
        self.run = run
        self.pdtype = DTYPES[run.param_dtype]
        self.cdtype = DTYPES[run.cache_dtype]
        self.mesh = _layout_mesh(run.model_parallel)

    # ---- params ------------------------------------------------------------

    def init(self, key: jax.Array, mesh=None):
        """Random parameters; with a mesh (``mesh``, else the model's
        own), built in place on it under the sharding rules by one jit'd
        init with ``out_shardings``."""
        init = lambda k: T.init_params(self.cfg, mode="init", key=k,
                                       dtype=self.pdtype,
                                       max_seq=self.run.max_seq)
        mesh = self.mesh if mesh is None else mesh
        if mesh is None:
            return init(key)
        shardings = tree_shardings(self.param_axes(), self.param_shapes(),
                                   mesh)
        return jax.jit(init, out_shardings=shardings)(key)

    def param_shapes(self):
        return T.init_params(self.cfg, mode="shape", dtype=self.pdtype,
                             max_seq=self.run.max_seq)

    def param_axes(self):
        return T.init_params(self.cfg, mode="axes", max_seq=self.run.max_seq)

    def param_count(self) -> int:
        shapes = self.param_shapes()
        return sum(int(jnp.prod(jnp.asarray(s.shape)))
                   for s in jax.tree.leaves(shapes))

    # ---- caches ------------------------------------------------------------

    def cache_shapes(self, batch: int, max_len: int):
        return T.cache_spec(self.cfg, batch, max_len, self.cdtype, "shape")

    def cache_init(self, batch: int, max_len: int):
        return T.cache_spec(self.cfg, batch, max_len, self.cdtype, "init")

    def cache_axes(self, batch: int, max_len: int):
        return T.cache_spec(self.cfg, batch, max_len, self.cdtype, "axes")

    def cache_leaf_counts(self) -> Tuple[int, int]:
        """(cache leaves the layer scan updates in place, all of them)."""
        return T.cache_leaf_counts(self.cfg)

    # ---- compute -----------------------------------------------------------

    def apply(self, params, tokens, *, extra_embeds=None, cache=None):
        return T.forward(params, self.cfg, tokens,
                         extra_embeds=extra_embeds, cache=cache,
                         backend=self.run.backend, remat=self.run.remat)

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """batch: {"tokens", "labels", "mask"?, "extra_embeds"?}."""
        logits, _, aux = self.apply(params, batch["tokens"],
                                    extra_embeds=batch.get("extra_embeds"))
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logits = mask_padded_vocab(logits, self.cfg.vocab_size)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(nll)
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        total = ce + aux
        return total, {"loss": total, "ce": ce, "aux": aux,
                       "tokens": denom}


def mask_padded_vocab(logits: jax.Array, vocab_size: int) -> jax.Array:
    """-inf out padded logit columns so softmax normalisation is exact."""
    if logits.shape[-1] == vocab_size:
        return logits
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    return jnp.where(iota < vocab_size, logits, -1e30)


def build(arch: str, run: RunConfig = RunConfig()) -> Model:
    return Model(get_config(arch), run)


# --------------------------------------------------------------------------
# assigned input shapes (the 4 shape cells)
# --------------------------------------------------------------------------

SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}


def cell_applicable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell runs, and why not if it doesn't."""
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention arch: 500k-context decode is "
                       "skipped per assignment (sub-quadratic archs only)")
    return True, ""


def input_specs(cfg: ModelConfig, shape: str,
                dtype=jnp.float32) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.
    No device allocation — feeds ``jit(...).lower()`` in the dry-run."""
    info = SHAPES[shape]
    B, S = info["global_batch"], info["seq_len"]
    kind = info["kind"]
    specs: Dict[str, Any] = {}
    tok_len = S if kind != "decode" else 1
    specs["tokens"] = jax.ShapeDtypeStruct((B, tok_len), jnp.int32)
    if kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["mask"] = jax.ShapeDtypeStruct((B, S), dtype)
    if cfg.frontend == "image_patches" and kind != "decode":
        specs["extra_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_len, cfg.d_model), dtype)
    if cfg.frontend == "audio_frames" and kind != "decode":
        ed = cfg.encoder.d_model or cfg.d_model
        specs["extra_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder.context, ed), dtype)
    return specs
