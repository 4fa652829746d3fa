#!/usr/bin/env python3
"""Find an open-loop cell's knee: its highest sustained request rate.

    python3 bench/sweep.py --workload <cell> --rates 50,100,200 --seconds 10

Runs the cell once per rate, in one process, with the mix's rate
replaced and everything else as ``bench/run.py`` runs it, and prints per
rate the end-to-end metrics, the requests that failed and the queue left
at the close.  A rate is sustained while the queue at the close stays
near empty and the tails stay flat; the cell's mix then takes about four
fifths of the highest such rate.  Used once, when a cell is defined.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
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
    for rate in [float(r) for r in args.rates.split(",")]:
        params = dict(cell.traffic.params, rate=rate)
        at = dataclasses.replace(cell, traffic=dataclasses.replace(
            cell.traffic, params=params))
        result, lines = run.run_cell(at, args.seed, args.seconds, False,
                                     device, peak,
                                     t_process=time.perf_counter())
        print(json.dumps({"rate": rate, "attempted": result["attempted"],
                          "failed": result["failed"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "notes": lines}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
