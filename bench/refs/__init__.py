"""Plain float32 references, one per model family, and of the kernels.

A reference imports nothing of the program: it reads the weights the
benchmark made from the seed, by the layout of the program's parameter
tree, and computes in straightforward ``jax.numpy`` under
``default_matmul_precision("highest")``.
"""
