"""Plain float32 reference of the qwen2 family (arXiv 2407.10671).

Pre-norm decoder: RMSNorm, grouped-query attention with a bias on q, k
and v and rotary positions (the rotate-half form, base ``rope_theta``),
then RMSNorm and a SiLU-gated MLP; a final RMSNorm and an untied head.
It follows the equations the program states, and where those depart
from the published model the departure is the program's and is noted:

* the token embedding is multiplied by sqrt(hidden_size) (Qwen2 does not
  scale it);
* every RMSNorm gain is stored as an offset from 1.

The weights are read by the layout of the program's parameter tree:
``scan/pos0/{attn,mlp}/...`` stacked over layers, ``embed`` (V, d) and
``lm_head`` (d, V), both padded past ``vocab_size`` rows/columns.  Each
layer is one jitted call in float32; the head is applied in row blocks.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.common import (gaps_from, head_scores, matmul, padded,
                               rmsnorm)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, dims, fp8: bool):
    mm = matmul(fp8)
    L, d = x.shape
    H, KV, hd, eps, theta = dims
    a, m = p["attn"], p["mlp"]
    h = rmsnorm(x, a["norm"], eps)
    q = mm("ld,dq->lq", h, a["wq"]) + a["bq"].astype(jnp.float32)
    k = mm("ld,dq->lq", h, a["wk"]) + a["bk"].astype(jnp.float32)
    v = mm("ld,dq->lq", h, a["wv"]) + a["bv"].astype(jnp.float32)
    pos = jnp.arange(L)
    q = _rope(q.reshape(L, H, hd), pos, theta)
    k = _rope(k.reshape(L, KV, hd), pos, theta)
    v = v.reshape(L, KV, hd)
    rep = H // KV
    causal = pos[None, :] <= pos[:, None]

    def group(g):                       # query heads g*rep .. g*rep+rep-1
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, 1)
        s = mm("lrh,mh->rlm", qg, k[:, g]) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        return mm("rlm,mh->lrh", jax.nn.softmax(s, -1), v[:, g])

    o = jax.lax.map(group, jnp.arange(KV))          # (KV, L, rep, hd)
    o = o.transpose(1, 0, 2, 3).reshape(L, H * hd)
    x = x + mm("lq,qd->ld", o, a["wo"])
    h = rmsnorm(x, m["norm"], eps)
    g = jax.nn.silu(mm("ld,df->lf", h, m["w_gate"]))
    u = mm("ld,df->lf", h, m["w_up"])
    return x + mm("lf,fd->ld", g * u, m["w_down"])


_layer_jit = jax.jit(_layer, static_argnames=("dims", "fp8"))
_embed_jit = jax.jit(lambda e, t, d: e[t].astype(jnp.float32) *
                     jnp.sqrt(jnp.float32(d)), static_argnums=2)


def _final(x, xc, norm, head, targets, vocab, eps):
    xc = None if xc is None else rmsnorm(xc, norm, eps)
    return head_scores(rmsnorm(x, norm, eps), head, targets, vocab, xc)


_final_jit = jax.jit(_final, static_argnames=("vocab", "eps"))


def score(params, config: Dict, tokens: np.ndarray, n_served: int,
          control: bool = False):
    """Gaps of the served tokens under the float32 reference: for each,
    the reference's best logit minus its logit of the served token (and,
    with ``control``, minus its logit of the control's first choice)."""
    toks, n = padded(tokens)
    targets = np.zeros_like(toks)
    targets[:n - 1] = toks[1:n]
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"], config["rope_theta"])
    stack = params["scan"]["pos0"]
    outs = []
    for fp8 in ((False, True) if control else (False,)):
        with jax.default_matmul_precision("highest"):
            x = _embed_jit(params["embed"], jnp.asarray(toks),
                           config["hidden_size"])
            for i in range(config["num_hidden_layers"]):
                p = jax.tree.map(lambda a: a[i], stack)
                x = _layer_jit(p, x, dims, fp8)
            outs.append(x)
    with jax.default_matmul_precision("highest"):
        best, at, ctrl = _final_jit(
            outs[0], outs[1] if control else None, params["final_norm"],
            params["lm_head"], jnp.asarray(targets), config["vocab_size"],
            config["rms_norm_eps"])
    return gaps_from(best, at, ctrl, n, n_served)
