"""Attention of one head over s positions, head size d, as the generated
kernel's graph states it: S = q kt + mask, softmax, S v.  FLOPs: q kt
and p v, 2 * s * s * d each.  Bytes: q, kt, v and the output (s x d
each) and the additive (s x s) mask, float32."""

from __future__ import annotations

from typing import Dict


def call(spec: Dict):
    s, d = spec["s"], spec["d"]
    return 4.0 * s * s * d, 4.0 * (4 * s * d + s * s)
