"""Decode attention of one step: each live slot's one query against the
keys and values of its valid context, every layer of the cut.

Bytes: the K and V rows up to each slot's valid length, the query and
the output.  FLOPs: q.k and p.v, 2 * 2 per element of K and V read.
"""

from __future__ import annotations

from typing import Dict

ITEM = {"bfloat16": 2, "float32": 4}


def step(config: Dict, active: int, valid_rows: int):
    """(flops, bytes) of one decode step whose ``active`` live slots
    attend ``valid_rows`` cache rows in total."""
    layers = config["num_hidden_layers"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    item = ITEM[config["run"]["cache_dtype"]]
    kv_bytes = 2 * valid_rows * KV * hd * item
    qo_bytes = 2 * active * H * hd * ITEM[config["run"]["activation_dtype"]]
    flops = 4 * valid_rows * H * hd
    return flops * layers, (kv_bytes + qo_bytes) * layers
