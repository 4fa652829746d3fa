"""Model FLOPs of one decode step of a qwen2-family cut: per live slot,
every matmul of every layer and the head (2 FLOPs per weight), plus
attention over the valid context (q.k and p.v)."""

from __future__ import annotations

from typing import Dict


def matmul_weights(config: Dict) -> int:
    d, ff = config["hidden_size"], config["intermediate_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return layer * config["num_hidden_layers"] + d * config["vocab_size"]


def step_flops(config: Dict, active: int, valid_rows: int) -> float:
    H, hd = config["num_attention_heads"], config["head_dim"]
    attn = 4 * valid_rows * H * hd * config["num_hidden_layers"]
    return 2.0 * matmul_weights(config) * active + attn
