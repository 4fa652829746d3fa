"""Compiler bridge: serve with pipeline-compiled, autotuned kernels.

Closes the loop from PR 6/7 (serving kernels and raised model blocks
compile through the PassManager stack) into the runtime: for one model
config, every raisable forward-pass block is compiled with
``pipeline.compile_traced`` under a schedule chosen by the autotuner
(``autotune.best_schedule`` on the block's dominant matmul shape, with
legality-driven fallbacks down to the nested schedule) and validated
against the traced reference on real inputs.  Blocks that do not raise,
do not lower, or do not validate fall back to plain jit — explicitly,
with the reason recorded, so the plan states exactly which blocks of the
serving model compile through the stack and which stay XLA.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional

import numpy as np

from repro.core.machine_model import TPU_V5E, MachineModel

_VALIDATE_RTOL = 1e-4


@dataclasses.dataclass
class BlockChoice:
    """Per-block outcome of the compile plan."""

    block: str
    status: str                       # "compiled" | "fallback"
    schedule: Optional[str] = None    # pipeline/schedule label
    cycles: Optional[int] = None      # machine-model cycles of the HwIR
    pallas: bool = False              # pallas kernel emitted and validated
    reason: str = ""                  # validation note or fallback cause

    def row(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ServeCompilePlan:
    """Which blocks of one serving model run through the compiler."""

    config: str
    choices: List[BlockChoice]
    machine: str = "tpu_v5e"

    @property
    def compiled(self) -> List[BlockChoice]:
        return [c for c in self.choices if c.status == "compiled"]

    @property
    def fallbacks(self) -> List[BlockChoice]:
        return [c for c in self.choices if c.status != "compiled"]

    def summary_rows(self) -> List[Dict]:
        return [c.row() for c in self.choices]

    def describe(self) -> str:
        lines = [f"// serve compile plan for {self.config} "
                 f"({len(self.compiled)}/{len(self.choices)} blocks "
                 f"compiled, machine={self.machine})"]
        for c in self.choices:
            if c.status == "compiled":
                lines.append(
                    f"//   {c.block}: COMPILED schedule={c.schedule} "
                    f"cycles={c.cycles} pallas={c.pallas} — {c.reason}")
            else:
                lines.append(f"//   {c.block}: FALLBACK plain jit — "
                             f"{c.reason}")
        return "\n".join(lines)


def _first_matmul_shape(graph) -> Optional[tuple]:
    for op in graph.ops:
        if op.opname == "matmul":
            m, k = op.inputs[0].type.shape
            _, n = op.inputs[1].type.shape
            return (m, n, k)
    return None


def _schedule_candidates(graph):
    """Ordered (schedule, tile) attempts: autotuned first, then the
    canned families, then the always-legal nested schedule."""
    cands = []
    mnk = _first_matmul_shape(graph)
    if mnk is not None:
        from repro.core import autotune
        sched, (tm, tn, tk) = autotune.best_schedule(*mnk)
        cands.append((f"autotuned:{sched}",
                      dict(schedule=sched,
                           tile={"m": tm, "n": tn, "k": tk})))
    cands.append(("tpu_mxu", dict(schedule="tpu_mxu")))
    cands.append(("nested", dict(schedule="nested")))
    return cands


def _validate(rg, ck, inputs, backend: str) -> None:
    want = rg.run_ref(*inputs)
    got = rg.run_compiled(ck, *inputs, backend=backend)
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=_VALIDATE_RTOL, atol=1e-5)


def plan_blocks(config_name: str, *, seq: int = 8, seed: int = 0,
                machine: MachineModel = TPU_V5E,
                validate: bool = True) -> ServeCompilePlan:
    """Build the per-block compile plan for one registry config."""
    raising = importlib.import_module("repro.core.raise")
    reports = raising.raise_model_blocks(config_name, seq=seq, seed=seed)
    choices: List[BlockChoice] = []
    for rep in reports:
        if not rep.ok:
            first = (rep.error or "unraisable").splitlines()[0]
            choices.append(BlockChoice(rep.block, "fallback", reason=first))
            continue
        rg = rep.raised
        if not rg.lowerable:
            choices.append(BlockChoice(
                rep.block, "fallback",
                reason=f"unlowerable ops: {', '.join(rg.unlowerable_ops)}"))
            continue
        choice = None
        last_err = "no schedule candidate"
        for label, kw in _schedule_candidates(rg.graph):
            try:
                ck = rg.compile(machine=machine, **kw)
            except Exception as e:                      # legality/lowering
                last_err = f"{label}: {str(e).splitlines()[0]}"
                continue
            note = "not validated"
            pallas = False
            if validate:
                try:
                    _validate(rg, ck, rep.example_inputs, "jax")
                    note = (f"validated jax backend vs reference at "
                            f"rtol={_VALIDATE_RTOL}")
                except Exception as e:
                    last_err = f"{label}: validation failed: " \
                               f"{str(e).splitlines()[0]}"
                    continue
                if ck.run_pallas is None:
                    note += f"; no pallas kernel: {ck.pallas_error}"
                else:
                    try:
                        _validate(rg, ck, rep.example_inputs, "pallas")
                        pallas = True
                        note += "; pallas validated"
                    except Exception as e:
                        note += (f"; pallas failed validation: "
                                 f"{str(e).splitlines()[0]}")
            choice = BlockChoice(
                rep.block, "compiled", schedule=label,
                cycles=int(ck.cycles.total), pallas=pallas, reason=note)
            break
        if choice is None:
            choice = BlockChoice(rep.block, "fallback", reason=last_err)
        choices.append(choice)
    return ServeCompilePlan(config=config_name, choices=choices,
                            machine=machine.name)
