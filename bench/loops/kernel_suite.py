"""The kernel cell: the compiler's generated kernels, back to back.

Set-up compiles each kernel of the mix through the compiler's own entry
points (``compile_gemm`` / ``autotune`` for a GEMM, ``compile_traced`` of
``flash_attention_graph`` for flash attention), makes its float32 inputs
on the chip from the seed in one jitted call, and compiles and warms the
jitted ``run_pallas``.  The window is split into equal parts, one per
kernel (in a traced run each part is at most the tracer's slice).  In
its part a kernel runs back to back in batches of calls, the host
waiting at the end of each batch, until the part is over; its time per
call is the part's measured length over its calls.  After the window the
last output of each kernel is compared with the plain reference.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.cells import Cell
from bench.loops import Outcome, peak_bytes
from bench.models.common import run_key
from bench.refs import gen_kernels as ref

BATCH_S = 0.1                       # one batch of calls lasts about this


def geomean_ms(per_call_s: Dict[str, float]) -> float:
    """gen_kernel_ms: the geometric mean over kernels of each kernel's
    seconds per call, in ms."""
    return math.exp(np.mean([math.log(v) for v in per_call_s.values()])) \
        * 1e3


@dataclasses.dataclass
class Kernel:
    name: str                       # e.g. gen_gemm; its work is bench/work
    kind: str                       # gemm | flash
    fn: Callable                    # the generated kernel's run_pallas
    shapes: List[Tuple[int, ...]]
    program: str                    # the jitted program's name in a trace
    spec: Dict


def build_kernels(mix: Dict, config: Dict, interpret=None) -> List[Kernel]:
    """The mix's kernels; a size given as a string is that key of the
    configuration (``"n": "intermediate_size"``)."""
    from repro.core import autotune
    from repro.core import frontend as fe
    from repro.core.pipeline import compile_gemm, compile_traced
    out = []
    for spec in mix["kernels"]:
        k = {key: config[v] if isinstance(v, str) and v in config else v
             for key, v in spec.items()}
        if k["kind"] == "gemm":
            m, n, kk = k["m"], k["n"], k["k"]
            if k["schedule"] == "autotune":
                sched, (tm, tn, tk) = autotune.best_schedule(m, n, kk)
            else:
                sched, (tm, tn, tk) = k["schedule"], k["tile"]
            ck = compile_gemm(m, n, kk, schedule=sched,
                              tile={"m": tm, "n": tn, "k": tk},
                              interpret=interpret, want_jax=False)
            shapes = [(m, kk), (kk, n)]
        elif k["kind"] == "flash":
            s, d = k["s"], k["d"]
            ck = compile_traced(fe.flash_attention_graph(s, s, d),
                                interpret=interpret, want_jax=False)
            shapes = [(s, d), (d, s), (s, d), (s, s)]
        else:
            raise ValueError(f"unknown kernel kind {k['kind']!r}")
        if ck.run_pallas is None:
            raise RuntimeError(f"{k['name']}: the pallas emitter refused: "
                               f"{ck.pallas_error}")
        out.append(Kernel(k["name"], k["kind"], ck.run_pallas, shapes,
                          ck.run_pallas.__name__, k))
    return out


def make_inputs(kernels: List[Kernel], key):
    """Every kernel's float32 inputs, on the chip, in one jitted call."""
    def make(key):
        out = []
        for kern, k in zip(kernels, jax.random.split(key, len(kernels))):
            ks = jax.random.split(k, len(kern.shapes))
            if kern.kind == "gemm":
                out.append([jax.random.normal(x, s, jnp.float32)
                            for x, s in zip(ks, kern.shapes)])
            else:
                (s, d) = kern.shapes[0]
                q = jax.random.normal(ks[0], (s, d)) / math.sqrt(d)
                kt = jax.random.normal(ks[1], (d, s))
                v = jax.random.normal(ks[2], (s, d))
                causal = jnp.tril(jnp.ones((s, s), bool))
                mask = jnp.where(causal, 0.0, -1e30).astype(jnp.float32)
                out.append([q, kt, v, mask])
        return out
    return jax.jit(make)(key)


def run(cell: Cell, seed: int, seconds: float, tracer, t_process: float,
        kernel_hook=None) -> Outcome:
    kernels = build_kernels(cell.traffic.params, cell.config)
    if kernel_hook is not None:
        kernels = kernel_hook(kernels)
    inputs = make_inputs(kernels, run_key(seed))
    calls, batch = [], []
    for kern, args in zip(kernels, inputs):
        f = jax.jit(kern.fn).lower(*args).compile()
        jax.block_until_ready(f(*args))
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        one = time.perf_counter() - t
        calls.append(f)
        batch.append(max(1, int(BATCH_S / max(one, 1e-6))))

    part = tracer.length(seconds / len(kernels))
    per_call, outs, counts, notes = {}, [], {}, []
    t0 = time.perf_counter()
    for kern, f, args, n in zip(kernels, calls, inputs, batch):
        start = time.perf_counter()
        end = start + part
        done, marks = 0, [start]
        with tracer.window(part):
            while True:
                with tracer.span("bench.step"):
                    for _ in range(n):
                        out = f(*args)
                with tracer.span("bench.sync"):
                    out.block_until_ready()
                done += n
                marks.append(time.perf_counter())
                if marks[-1] >= end:
                    break
        per_call[kern.name] = (marks[-1] - start) / done
        counts[kern.name] = done
        outs.append(out)
        each = np.diff(marks) / n * 1e3
        notes.append(f"{kern.name}: batches={len(each)} of {n} calls, ms "
                     f"per call min={float(each.min())!r} median="
                     f"{float(np.median(each))!r} max={float(each.max())!r}")
    t1 = time.perf_counter()

    metrics = {"setup_s": t0 - t_process, "gen_kernel_ms": geomean_ms(per_call)}
    notes += [f"{k}: calls={counts[k]} ms_per_call={v * 1e3!r}"
              for k, v in per_call.items()]
    memory = peak_bytes()
    checks = []
    with jax.default_matmul_precision("highest"):
        for kern, args, out in zip(kernels, inputs, outs):
            want = ref.REFS[kern.kind](*args)
            err = float(ref.rel_err(out, want))
            checks.append((f"{kern.name}_err", err,
                           float(cell.limits[f"{kern.name}_err"])))
    record = {"t0": t0, "t1": t1, "kernels": kernels, "config": cell.config,
              "per_call_s": per_call}
    return Outcome(metrics, sum(counts.values()), 0, checks, memory, record,
                   notes)
