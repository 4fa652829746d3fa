#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, limits and per-layer metrics are files under
``bench/`` found by name (``bench/cells.py``).  The run makes its inputs
and weights from ``--seed``, warms up every program the window drives
(set-up, reported as ``setup_s``), measures for ``--seconds``, then
checks what the timed path produced against the plain reference.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window, cut to at most ``TRACE_SLICE_S`` seconds, is
traced by the profiler and the metrics are the cell's per-layer metrics,
read from the trace by ``bench/metrics/<metric>.py``; a traced run in
which one of them finds nothing to read fails.  Earlier lines describe
the run (compiles inside the window, the generator's lateness, the
reference's work); the last line of standard output is one JSON object,
and the last lines of standard error give each number compared beside
its limit.

It exits non-zero, printing no result, where JAX finds no TPU, fewer
chips than the cell asks for, or a device kind ``bench/peaks.json`` does
not know.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT                  # ``bench`` as a package, not its files
sys.path.insert(1, os.path.join(ROOT, "src"))

TRACE_SLICE_S = 10.0                # seconds of the window a trace covers
# a compilation, or a program read back from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoDevice(RuntimeError):
    pass


def find_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found platform {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found "
                       f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def device_peak(kind: str) -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts XLA compilations inside the window (JAX's monitoring
    events)."""

    def __init__(self, tracer):
        import jax
        self.tracer = tracer
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.tracer.in_window and name in COMPILE_EVENTS:
            self.count += 1


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             peak: dict, t_process: float = T_PROCESS, hooks=None,
             keep_trace=None):
    """Run ``cell`` on the device already found; returns the result's
    dict (the last line) and the earlier lines."""
    from bench.tracer import Tracer
    loop = importlib.import_module(f"bench.loops.{cell.traffic.loop}")
    tracer = Tracer(trace, TRACE_SLICE_S)
    counter = CompileCounter(tracer)
    try:
        out = loop.run(cell, seed, seconds, tracer, t_process,
                       **(hooks or {}))
        lines = list(out.notes)
        lines.append(f"compiles inside the window: {counter.count}")
        dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
        result = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed}
        metrics = {}
        if trace:
            red = tracer.reduce()
            if keep_trace:
                tracer.keep(keep_trace)
            rec = dict(out.record, slices=tracer.slices)
            from bench.cells import load_file_module
            for m in cell.per_layer:
                v = load_file_module("metrics", m["name"]).read(red, rec,
                                                               peak)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            silent = [m["name"] for m in cell.per_layer
                      if m["name"] not in metrics]
            if silent:
                raise RuntimeError(
                    f"the trace holds nothing for {silent}, which "
                    f"BENCHMARK.json lists for {cell.name}: a program or "
                    f"kernel they read may have been renamed")
            dev.update(busy_s=red.busy_s, window_s=red.window_s)
            result["breakdown"] = red.breakdown()
            lines.append("traced programs (runs, device s): " + json.dumps(
                {k: v for k, v in red.modules.items()}))
        else:
            from bench.cells import quantity
            for m in cell.end_to_end:
                q = m["name"] if m["name"] in out.metrics \
                    else quantity(m["name"])
                if q not in out.metrics:
                    raise RuntimeError(f"the run gave no {m['name']}")
                metrics[m["name"]] = {"value": out.metrics[q],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dev
        result["checks"] = {name: {"value": v, "limit": lim}
                            for name, v, lim in out.checks}
        lines += [f"[check] {name}: value={v!r} limit={lim!r} "
                  f"{'ok' if v <= lim else 'FAIL'}"
                  for name, v, lim in out.checks]
        return result, lines
    finally:
        tracer.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the trace files of --trace 1 to DIR")
    args = ap.parse_args(argv)

    from bench import cells
    cell = cells.load_cell(args.workload, cells.load_benchmark())
    try:
        device = find_device(cell.chips)
        peak = device_peak(device["kind"])
    except NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    from repro.device import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] {cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={device} cache={cache}", flush=True)

    result, lines = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), device, peak,
                             keep_trace=args.keep_trace)
    for line in lines:
        print(f"[bench] {line}", flush=True)
    for line in lines:
        if line.startswith("[check]"):
            print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
