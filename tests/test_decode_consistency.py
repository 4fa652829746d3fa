"""Decode-vs-teacher-forcing logits consistency for every arch family —
the serving-correctness gate (KV caches, recurrent states, cross-attn
caches, compressed MLA caches all exercised)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCHS, get_config, reduced
from repro.models.model import Model, RunConfig

PREFILL, DECODE, MAXLEN = 8, 4, 32


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        # exactness requires no capacity drops (see test_models_smoke for
        # the dropping behaviour itself)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
    model = Model(cfg, RunConfig(max_seq=MAXLEN))
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, PREFILL + DECODE
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    ee = None
    if cfg.frontend == "image_patches":
        ee = 0.1 * jnp.ones((B, cfg.frontend_len, cfg.d_model))
    if cfg.frontend == "audio_frames":
        ee = 0.1 * jnp.ones((B, cfg.encoder.context,
                             cfg.encoder.d_model or cfg.d_model))

    full, _, _ = model.apply(params, tokens, extra_embeds=ee)
    cache = model.cache_init(B, MAXLEN)
    pre, cache, _ = model.apply(params, tokens[:, :PREFILL],
                                extra_embeds=ee, cache=cache)
    errs = [float(jnp.abs(pre - full[:, :PREFILL]).max())]
    for t in range(PREFILL, S):
        lg, cache, _ = model.apply(params, tokens[:, t:t + 1], cache=cache)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, t]).max()))
    assert max(errs) < 2e-4, f"{arch}: decode drift {errs}"


@pytest.mark.parametrize("arch,backend", [
    ("qwen2_7b", "xla"), ("qwen2_7b", "pallas"), ("gemma3_4b", "xla"),
    ("recurrentgemma_2b", "xla"), ("mamba2_130m", "xla")])
def test_slot_vmapped_decode_matches_full_forward(arch, backend):
    """Decode as the serving engine runs it: B=1 caches stacked along a
    slot axis, each slot prefilled to its own length, one vmapped step
    for all.  Appended K/V is written in the layer scan's carry at each
    slot's own row; states rewritten whole go through the scan's xs/ys.
    Every slot's logits match the no-cache forward on its prefix."""
    cfg = reduced(get_config(arch))
    model = Model(cfg, RunConfig(max_seq=MAXLEN, backend=backend))
    params = model.init(jax.random.PRNGKey(1))
    prefill = (3, PREFILL, 5)
    S = max(prefill) + DECODE
    tokens = jax.random.randint(jax.random.PRNGKey(2), (len(prefill), S), 0,
                                cfg.vocab_size)
    full, _, _ = model.apply(params, tokens)
    caches = []
    for s, n in enumerate(prefill):
        _, c, _ = model.apply(params, tokens[s:s + 1, :n],
                              cache=model.cache_init(1, MAXLEN))
        caches.append(c)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *caches)
    step = jax.jit(jax.vmap(lambda c, t: model.apply(
        params, t[None, None], cache=c)[:2]))
    errs = []
    for t in range(DECODE):
        lg, stacked = step(stacked, jnp.stack(
            [tokens[s, n + t] for s, n in enumerate(prefill)]))
        errs += [float(jnp.abs(lg[s, 0, 0] - full[s, n + t]).max())
                 for s, n in enumerate(prefill)]
    assert max(errs) < 2e-4, f"{arch}/{backend}: decode drift {errs}"
    assert [int(n) for n in stacked["len"]] == [n + DECODE for n in prefill]


def test_cache_len_tracks():
    cfg = reduced(get_config("qwen2_7b"))
    model = Model(cfg, RunConfig(max_seq=MAXLEN))
    params = model.init(jax.random.PRNGKey(0))
    cache = model.cache_init(1, MAXLEN)
    assert int(cache["len"]) == 0
    tok = jnp.zeros((1, 5), jnp.int32)
    _, cache, _ = model.apply(params, tok, cache=cache)
    assert int(cache["len"]) == 5
    _, cache, _ = model.apply(params, tok[:, :1], cache=cache)
    assert int(cache["len"]) == 6
