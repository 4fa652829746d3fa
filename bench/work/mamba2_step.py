"""One decode step of a mamba2-family model, from the math's shapes.

Per live slot and layer: the input projection (to the gate z, the
convolved stream xBC and the step dt) and the output projection, 2
FLOPs per weight; the depthwise convolution over the last ``d_conv``
positions of xBC, 2 per tap; and per SSD head the state update
``S = exp(dt A) S + dt x B^T`` (3 per element of the headdim x d_state
state: the decay, the outer product, the sum) and its readout ``S C``
(2 per element).  Then the tied head, 2 per weight.

Bytes: every weight the step needs, read once (in ``param_dtype``; of
the embedding only the head's ``vocab_size`` rows), and per live slot
and layer its SSM state read and written (float32, as the configuration
states) and its convolution window of ``d_conv - 1`` positions read and
written (in ``cache_dtype``).
"""

from __future__ import annotations

from typing import Dict

from bench.work.decode_attention import ITEM

STATE_ITEM = 4                      # the SSM state is float32


def _dims(config: Dict):
    d = config["d_model"]
    d_inner = config["expand"] * d
    n = config["d_state"]
    heads = d_inner // config["headdim"]
    conv_dim = d_inner + 2 * config["ngroups"] * n
    return d, d_inner, n, heads, conv_dim


def matmul_weights(config: Dict) -> int:
    """Weights of every projection and the head."""
    d, d_inner, n, heads, conv_dim = _dims(config)
    layer = d * (d_inner + conv_dim + heads) + d_inner * d
    return layer * config["n_layer"] + d * config["vocab_size"]


def step_flops(config: Dict, active: int) -> float:
    d, d_inner, n, heads, conv_dim = _dims(config)
    ssd = 5 * d_inner * n                       # heads x headdim x d_state
    conv = 2 * config["d_conv"] * conv_dim
    per_slot = 2 * matmul_weights(config) + \
        (ssd + conv) * config["n_layer"]
    return float(per_slot) * active


def step_bytes(config: Dict, active: int) -> float:
    d, d_inner, n, heads, conv_dim = _dims(config)
    # per layer: the conv filter, three per-head vectors (A_log, D,
    # dt_bias) and the two norms' gains; then the final norm
    small = config["d_conv"] * conv_dim + 3 * heads + d + d_inner
    weights = (matmul_weights(config) + small * config["n_layer"] + d) * \
        ITEM[config["run"]["param_dtype"]]
    state = d_inner * n * STATE_ITEM
    conv = (config["d_conv"] - 1) * conv_dim * \
        ITEM[config["run"]["cache_dtype"]]
    return float(weights) + 2.0 * (state + conv) * config["n_layer"] * active
