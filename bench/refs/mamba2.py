"""Plain float32 reference of the mamba2 family (arXiv 2405.21060).

Each block: RMSNorm, one input projection split into the gate z, the
convolved stream xBC and the step dt; a causal depthwise convolution of
width ``d_conv`` over xBC and SiLU; then the selective state-space
recurrence, one token at a time, per head h of ``headdim`` channels:

    dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (headdim x d_state)
    y_t  = S_t C_t + D x_t

and the gated norm RMSNorm(y * SiLU(z)) before the output projection; a
final RMSNorm and the head tied to the embedding.  It follows the
equations the program states; where those depart from the published
model the departure is the program's and is noted:

* the token embedding is multiplied by sqrt(d_model) on the way in (the
  tied head reads it unscaled);
* the convolution has no bias;
* every RMSNorm gain is stored as an offset from 1.

The recurrence is a plain scan over tokens, not the chunked (SSD) form
the program computes, so the two agree only if both are right.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.common import (gaps_from, head_scores, matmul, padded,
                               rmsnorm)


def _layer(p, x, dims, fp8: bool):
    mm = matmul(fp8)
    d_inner, N, H, P, W, eps = dims
    L = x.shape[0]
    h = rmsnorm(x, p["norm"], eps)
    proj = mm("ld,dk->lk", h, p["in_proj"])
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:2 * d_inner + 2 * N]
    dt = proj[:, 2 * d_inner + 2 * N:]
    w = p["conv"].astype(jnp.float32)                      # (W, C)
    ext = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc], 0)
    conv = sum(ext[i:i + L] * w[i] for i in range(W))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(L, H, P)
    B, C = xbc[:, d_inner:d_inner + N], xbc[:, d_inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))   # (L, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)

    def step(S, inp):
        dt_t, x_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", S, c_t,
                       precision=jax.lax.Precision.HIGHEST) + D[:, None] * x_t
        return S, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (dt, xs, B, C))
    y = y.reshape(L, d_inner) * jax.nn.silu(z)
    y = rmsnorm(y, p["out_norm"], eps)
    return x + mm("lk,kd->ld", y, p["out_proj"])


_layer_jit = jax.jit(_layer, static_argnames=("dims", "fp8"))
_embed_jit = jax.jit(lambda e, t, d: e[t].astype(jnp.float32) *
                     jnp.sqrt(jnp.float32(d)), static_argnums=2)


def _final(x, xc, norm, embed, targets, vocab, eps):
    xc = None if xc is None else rmsnorm(xc, norm, eps)
    return head_scores(rmsnorm(x, norm, eps), embed.T, targets, vocab, xc)


_final_jit = jax.jit(_final, static_argnames=("vocab", "eps"))


def score(params, config: Dict, tokens: np.ndarray, n_served: int,
          control: bool = False):
    """Gaps of the served tokens under the float32 reference (see
    ``bench/refs/qwen2.py``)."""
    toks, n = padded(tokens)
    targets = np.zeros_like(toks)
    targets[:n - 1] = toks[1:n]
    d_inner = config["expand"] * config["d_model"]
    dims = (d_inner, config["d_state"], d_inner // config["headdim"],
            config["headdim"], config["d_conv"], config["rms_norm_eps"])
    stack = params["scan"]["pos0"]["ssd"]
    outs = []
    for fp8 in ((False, True) if control else (False,)):
        with jax.default_matmul_precision("highest"):
            x = _embed_jit(params["embed"], jnp.asarray(toks),
                           config["d_model"])
            for i in range(config["n_layer"]):
                x = _layer_jit(jax.tree.map(lambda a: a[i], stack), x, dims,
                               fp8)
            outs.append(x)
    with jax.default_matmul_precision("highest"):
        best, at, ctrl = _final_jit(
            outs[0], outs[1] if control else None, params["final_norm"],
            params["embed"], jnp.asarray(targets), config["vocab_size"],
            config["rms_norm_eps"])
    return gaps_from(best, at, ctrl, n, n_served)
