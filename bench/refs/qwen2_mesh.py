"""The plain float32 reference of the ``qwen2_mesh`` family: the qwen2
reference (``bench/refs/qwen2.py``), which runs layer by layer and reads
the weights sharded as they lie."""

from bench.refs.qwen2 import score  # noqa: F401
