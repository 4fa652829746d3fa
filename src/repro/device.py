"""Choices taken from the platform the program runs on, in one place.

* Pallas kernels run in the interpreter only where JAX's backend is the
  CPU; on an accelerator they lower through Mosaic.
* JAX's persistent compilation cache lives where
  ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at a fixed path inside
  the checkout (the path is part of the cache key, so it never moves).
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def pallas_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a pallas kernel runs in the interpreter.  ``None`` derives
    it from the backend; an explicit value wins (a CPU process compiling
    Mosaic kernels for a described TPU passes ``False``)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    JAX itself reads ``JAX_COMPILATION_CACHE_DIR``; when it is set nothing
    else is configured here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
