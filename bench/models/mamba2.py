"""mamba2 configurations on the program's ``mamba2_130m`` registry entry."""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.models.common import program_model, seeded_params
from repro.configs.base import SSMConfig


def build(config: Dict):
    d = config["d_model"]
    d_inner = config["expand"] * d
    ssm = SSMConfig(d_inner=d_inner, head_dim=config["headdim"],
                    state_dim=config["d_state"], conv_width=config["d_conv"],
                    chunk=config["chunk_size"])
    if config["ngroups"] != 1:
        raise ValueError("the program's SSD block has one B/C group")
    model = program_model(
        config,
        overrides=dict(num_layers=config["n_layer"],
                       vocab_size=config["vocab_size"],
                       norm_eps=config["rms_norm_eps"], ssm=ssm,
                       num_heads=d_inner // config["headdim"],
                       num_kv_heads=d_inner // config["headdim"]),
        widths=dict(d_model=d, tie_embeddings=config["tie_embeddings"]))
    return model, config["slots"], config["max_len"]


def _special(name: str, shape, key):
    """The paper's initialisation of the decay and step: A uniform in
    [1, 16] (A_log = log A), dt log-uniform in [0.001, 0.1] through the
    inverse softplus (dt_bias)."""
    if name.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name.endswith("dt_bias"):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.endswith("/D"):
        return jnp.ones(shape, jnp.float32)
    return None


def init_params(model, config: Dict, key):
    return seeded_params(model, key, special=_special)
