"""C = A @ B, (m, k) x (k, n), float32 operands and result."""

from __future__ import annotations

from typing import Dict


def call(spec: Dict):
    m, n, k = spec["m"], spec["n"], spec["k"]
    return 2.0 * m * n * k, 4.0 * (m * k + k * n + m * n)
