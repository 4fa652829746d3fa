"""qwen2 configurations on the program's ``qwen2_7b`` registry entry."""

from __future__ import annotations

from typing import Dict

from bench.models.common import program_model, seeded_params


def build(config: Dict):
    """(Model, slots, max_len): the registry entry at the file's depth and
    rope base; every width must already match the file."""
    model = program_model(
        config,
        overrides=dict(num_layers=config["num_hidden_layers"],
                       rope_theta=config["rope_theta"],
                       norm_eps=config["rms_norm_eps"]),
        widths=dict(d_model=config["hidden_size"],
                    d_ff=config["intermediate_size"],
                    num_heads=config["num_attention_heads"],
                    num_kv_heads=config["num_key_value_heads"],
                    head_dim=config["head_dim"],
                    vocab_size=config["vocab_size"],
                    qkv_bias=config["attention_bias"],
                    tie_embeddings=config["tie_word_embeddings"]))
    return model, config["slots"], config["max_len"]


def init_params(model, config: Dict, key):
    return seeded_params(model, key)
