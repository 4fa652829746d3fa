#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process, this runs the cell as ``bench/run.py``
does (set-up, a window of ``--seconds`` at the cell's own load, the
check against the plain reference) and prints the program's reading of
each compared number.  Beside it, on the same prompts and served tokens
(serving cells) or the same inputs (the kernel cell), it reads the
control: the plain reference computed with float8_e4m3 operands, the
precision below the configuration's, which must come out as not
correct.  It prints one JSON line per seed and a summary last.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def _serving_spy(ref, readings):
    """Wrap the family's ``score`` so each scored request also yields the
    control's gaps."""
    score = ref.score

    def spy(params, config, tokens, n_served, control=False):
        gap, ctrl = score(params, config, tokens, n_served, control=True)
        readings.append((float(gap.max()), float(ctrl.max()), n_served))
        return gap, ctrl
    return score, spy


def control_kernels(cell, seed):
    import jax
    from bench.loops import kernel_suite
    from bench.models.common import run_key
    from bench.refs import gen_kernels as ref
    kernels = kernel_suite.build_kernels(cell.traffic.params, cell.config)
    inputs = kernel_suite.make_inputs(kernels, run_key(seed))
    out = {}
    with jax.default_matmul_precision("highest"):
        for kern, args in zip(kernels, inputs):
            want = ref.REFS[kern.kind](*args)
            got = ref.REFS[kern.kind](*args, fp8=True)
            out[f"{kern.name}_err"] = float(ref.rel_err(got, want))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import cells, run
    cell = cells.load_cell(args.workload, cells.load_benchmark())
    try:
        device = run.find_device(cell.chips)
        peak = run.device_peak(device["kind"])
    except run.NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    from repro.device import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    summary = {"program": {}, "control": {}}
    for seed in [int(s) for s in args.seeds.split(",")]:
        readings = []
        if cell.traffic.loop != "kernel_suite":
            ref = cells.family_module("refs", cell.config)
            score, ref.score = _serving_spy(ref, readings)
        try:
            result, _ = run.run_cell(cell, seed, args.seconds, False, device,
                                     peak, t_process=time.perf_counter())
        finally:
            if cell.traffic.loop != "kernel_suite":
                ref.score = score
        prog = {k: v["value"] for k, v in result["checks"].items()}
        if cell.traffic.loop == "kernel_suite":
            ctrl = control_kernels(cell, seed)
        else:
            ctrl = {"logit_gap": max(c for _, c, _ in readings)}
        line = {"seed": seed, "program": prog, "control": ctrl,
                "per_request": readings, "metrics": result["metrics"]}
        print(json.dumps(line), flush=True)
        for k, v in prog.items():
            summary["program"].setdefault(k, []).append(v)
        for k, v in ctrl.items():
            summary["control"].setdefault(k, []).append(v)
    summary["lower"] = {k: max(v) for k, v in summary["program"].items()}
    summary["upper"] = {k: min(v) for k, v in summary["control"].items()}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
