#!/usr/bin/env python
"""Generate docs/RAISING.md — the traced-JAX -> TensorIR raising guide.

Every transcript and every row of the raisability table is produced by
actually running the raiser (via the ``reproc`` driver or
``raise_model_blocks``) in-process, so the document cannot drift from
the compiler's real behaviour: CI regenerates it and fails on any diff
(same contract as docs/PASSES.md / docs/LOWERING.md).

    PYTHONPATH=src python scripts/gen_raising_md.py > docs/RAISING.md
    # or: make docs
"""

from __future__ import annotations

import importlib
import io
import sys

from repro.configs.base import ARCHS
from repro.core import reproc

raising = importlib.import_module("repro.core.raise")


def run_reproc(*argv: str) -> str:
    buf = io.StringIO()
    rc = reproc.main(list(argv), out=buf)
    if rc != 0:
        raise RuntimeError(f"reproc {' '.join(argv)} exited {rc}")
    return buf.getvalue().rstrip("\n")


def block(cmd_args: list, lang: str = "") -> str:
    shown = "PYTHONPATH=src python -m repro.core.reproc " + " ".join(cmd_args)
    out = run_reproc(*cmd_args)
    return (f"```sh\n{shown}\n```\n\n"
            f"```{lang}\n{out}\n```")


def raisability_table() -> str:
    """One row per config: which forward-pass blocks raise, and why the
    rest don't (first primitive outside the vocabulary)."""
    lines = ["| config | raised blocks | blocked blocks (first unraisable primitive) |",
             "|---|---|---|"]
    for arch in ARCHS:
        reports = raising.raise_model_blocks(arch)
        ok = [r.block for r in reports if r.ok]
        bad = []
        for r in reports:
            if r.ok:
                continue
            prim = "?"
            if r.error and "[primitive: " in r.error:
                prim = r.error.split("[primitive: ")[1].split("]")[0]
            bad.append(f"{r.block} ({prim})")
        lines.append(f"| {arch} | {', '.join(ok)} | {', '.join(bad) or '—'} |")
    return "\n".join(lines)


def main() -> int:
    transcript = block(["--raise", "qwen2_7b:mlp"])
    report = block(["--raise", "mamba2_130m"])
    cosim = block(["--raise", "qwen2_7b:rmsnorm",
                   "--pipeline", "lower{tile_m=8,tile_n=8,tile_k=8}",
                   "--simulate"])
    table = raisability_table()

    print(f"""\
# Raising traced JAX into TensorIR

<!-- GENERATED FILE — do not edit.  Regenerate with `make docs`
     (scripts/gen_raising_md.py); CI diffs this against live output. -->

The paper's flow starts from an IR produced by someone else's tracer;
this repo's equivalent is `repro.core.raise`: it walks the jaxpr of a
traced JAX function and **raises** it into the rank-2 TensorIR that the
rest of the stack (PassManager -> LoopIR -> HwIR -> {{ref, jax, pallas}}
backends, DSE, cosim) already compiles.  Anything the vocabulary cannot
express is rejected with a diagnostic naming the primitive and the
offending equation — raising is total-or-loud, never silently wrong.

## Vocabulary

| jax primitive(s) | TensorIR |
|---|---|
| `dot_general` (single contraction, no batch dims) | `matmul` |
| `add sub mul div max` | binary ewise |
| `exp neg tanh logistic sqrt rsqrt log1p abs` | unary ewise |
| `reduce_sum` / `reduce_max` over the last axis | carried `reduce` |
| `lax.scan` of `h = a*h + u` / `jnp.cumsum(axis=0)` | carried `scan` (linear / cumsum) |
| `transpose` (rank-2) / unit-dim `reshape` `squeeze` `broadcast_in_dim` | `transpose` / shape bookkeeping |
| `jit` `custom_jvp_call` `stop_gradient` `convert_element_type` ... | inlined / identity |

Constants (weights captured by the traced function, literals, anything
foldable) are folded in numpy while possible and **lazily materialised**
as extra graph inputs (`c0`, `c1`, ...) the first time a non-foldable op
consumes them — deduplicated by value, rebound by `RaisedGraph.bind`.

## Raising one block

`reproc --raise CONFIG:BLOCK` raises one forward-pass region of a
(reduced) model config and prints the TensorIR module; everything the
driver does with a hand-built graph (`--pipeline`, `--emit`,
`--simulate`, `--dse`) composes with it.

{transcript}

## The raisability report

`reproc --raise CONFIG` (no block) prints one line per region: raised
(op/const counts) or the diagnostic explaining why not.

{report}

## Through the rest of the stack

A raised graph is an ordinary TensorIR graph: it lowers, schedules,
emits hardware, and co-simulates against the numpy oracle.

{cosim}

## Raisability across the registry

Generated by raising every block of every config at the reduced shapes
(`raise_model_blocks`); the differential test matrix
(`tests/test_raise.py`) additionally checks every raised block against
the traced JAX function at 1e-4 on the graph interpreter and through
the compiled ref/jax backends.

{table}

## API

```python
import repro.core.frontend as fe

rg = fe.raise_jaxpr(fn, (8, 64), (64, 256))   # shapes or arrays
rg.graph          # TensorIR Graph (args first, then captured consts)
rg.run_ref(x, w)  # graph-interpreter execution
ck = rg.compile(tile={{"m": 8, "n": 8, "k": 8}})
rg.run_compiled(ck, x, w, backend="jax")
rg.explore(validate_top=1)                    # DSE over the raised region

reports = fe.raise_model_blocks("qwen2_7b")   # the per-config corpus
```

`raise_jaxpr(..., check_hlo_trips=True)` additionally compiles the
function through XLA and cross-checks every scan length recovered by
raising against the `while`-loop trip counts `launch.hlo_analysis`
walks out of the optimized HLO — the two independent views of the
recurrence must agree.""")
    return 0


if __name__ == "__main__":
    sys.exit(main())
