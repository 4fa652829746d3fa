"""Serving-under-load benchmark — the recorded perf trajectory's first entry.

Drives the batched continuous engine (``repro.serve.continuous``) with a
deterministic mixed prefill/decode workload from ``repro.serve.loadgen``
(Poisson or bursty arrivals, mixed prompt/output lengths, replayable
seed), records TTFT/TPOT/e2e latency and queue depth through
``repro.serve.metrics``, and writes ``BENCH_serve.json``: tokens/sec,
p50/p90/p99 TTFT and TPOT, slot utilization and requests completed per
config — so every future PR shows measured serving deltas instead of
claims.

The per-block compiler bridge (``repro.serve.compiled``) runs first and
its plan is embedded per entry: which forward-pass blocks of the serving
model compiled through the PassManager stack under autotuned schedules
(validated against the traced reference) and which fell back to plain
jit, with reasons.

  PYTHONPATH=src python benchmarks/serve_bench.py                 # 2 configs
  PYTHONPATH=src python benchmarks/serve_bench.py --smoke         # CI seconds
  PYTHONPATH=src python benchmarks/serve_bench.py --clock virtual # replayable
  PYTHONPATH=src python benchmarks/serve_bench.py --mesh model=2  # sharded
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional

import numpy as np

REQUIRED_METRIC_KEYS = ("tokens_per_s", "ttft", "tpot", "e2e",
                        "queue_depth", "slot_utilization", "requests")
REQUIRED_PCTL_KEYS = ("p50", "p90", "p99")


def parse_mesh(spec: Optional[str]):
    """"data=2,model=2" -> an active jax mesh, or None."""
    if not spec:
        return None
    import jax
    axes, sizes = [], []
    for part in spec.split(","):
        name, _, n = part.partition("=")
        axes.append(name.strip())
        sizes.append(int(n))
    need = int(np.prod(sizes))
    if len(jax.devices()) < need:
        raise SystemExit(
            f"mesh {spec} needs {need} devices, only {len(jax.devices())} "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}")
    from repro.launch.mesh import make_mesh
    return make_mesh(sizes, axes)


def run_config(name: str, *, slots: int, requests: int, rate: float,
               process: str, seed: int, clock_kind: str,
               queue_limit: Optional[int], prompt_hi: int, out_hi: int,
               with_plan: bool, mesh=None, max_len: int = 64) -> Dict:
    import jax
    from repro.configs.base import get_config, reduced
    from repro.models.model import Model, RunConfig
    from repro.serve import loadgen
    from repro.serve.continuous import ContinuousEngine, Request
    from repro.serve.metrics import ServeMetrics, VirtualClock, WallClock

    cfg = reduced(get_config(name))
    model = Model(cfg, RunConfig(max_seq=max_len))
    params = model.init(jax.random.PRNGKey(seed), mesh=mesh)

    plan = None
    if with_plan:
        from repro.serve.compiled import plan_blocks
        plan = plan_blocks(name, seed=seed)

    load = loadgen.LoadConfig(
        num_requests=requests, vocab_size=cfg.vocab_size, seed=seed,
        process=process, rate=rate,
        prompt=loadgen.LengthDist("uniform", 4, prompt_hi),
        output=loadgen.LengthDist("uniform", 2, out_hi))
    stream = loadgen.generate_stream(load)

    clock = VirtualClock() if clock_kind == "virtual" else WallClock()
    metrics = ServeMetrics(clock, slots=slots)
    engine = ContinuousEngine(model, params, slots=slots, max_len=max_len,
                              queue_limit=queue_limit, metrics=metrics,
                              plan=plan, mesh=mesh)

    i = 0
    while i < len(stream) or engine.busy:
        now = clock.now()
        while i < len(stream) and stream[i].arrival <= now:
            r = stream[i]
            if not engine.submit(Request(r.rid, r.prompt, r.max_new),
                                 arrival=r.arrival):
                break                         # backpressure: head waits
            i += 1
        if engine.step() == 0 and i < len(stream):
            # idle before the next arrival: jump a virtual clock, yield a
            # wall clock
            gap = stream[i].arrival - clock.now()
            if gap > 0:
                if clock.kind == "virtual":
                    clock.advance(gap)
                else:
                    time.sleep(min(gap, 0.01))

    entry = {
        "config": name,
        "slots": slots,
        "max_len": max_len,
        "queue_limit": queue_limit,
        "mesh": None if mesh is None else
                {a: int(s) for a, s in mesh.shape.items()},
        "workload": load.describe(),
        "stream_digest": list(loadgen.stream_digest(stream)),
        "metrics": metrics.snapshot(),
        "requests_completed": len(engine.results),
    }
    if plan is not None:
        entry["compiled_blocks"] = plan.summary_rows()
        entry["compiled_count"] = len(plan.compiled)
    return entry


def check_bench(doc: Dict) -> None:
    """Schema gate for BENCH_serve.json (used by CI serve-smoke)."""
    if doc.get("schema") != "serve_bench/v1":
        raise ValueError(f"bad schema {doc.get('schema')!r}")
    entries = doc.get("entries")
    if not entries:
        raise ValueError("no entries")
    for e in entries:
        m = e.get("metrics", {})
        for k in REQUIRED_METRIC_KEYS:
            if k not in m:
                raise ValueError(f"{e.get('config')}: missing metric {k!r}")
        for h in ("ttft", "tpot", "e2e"):
            for k in REQUIRED_PCTL_KEYS:
                if k not in m[h]:
                    raise ValueError(f"{e.get('config')}: {h} missing {k!r}")
        if m["tokens_per_s"] <= 0:
            raise ValueError(f"{e.get('config')}: tokens_per_s "
                             f"{m['tokens_per_s']} <= 0")
        if not 0 < e["requests_completed"] <= m["requests"]["submitted"]:
            raise ValueError(f"{e.get('config')}: request accounting "
                             f"mismatch: {e['requests_completed']} completed "
                             f"of {m['requests']['submitted']} submitted")


def fmt_entry(e: Dict) -> str:
    m = e["metrics"]
    unit = "s" if m["clock"] == "wall" else "step"
    return (f"[serve_bench] {e['config']:16s} slots={e['slots']} "
            f"req={e['requests_completed']}/{m['requests']['submitted']} "
            f"tok/{unit}={m['tokens_per_s']:.1f} "
            f"ttft p50/p99={m['ttft']['p50']:.3g}/{m['ttft']['p99']:.3g} "
            f"tpot p50/p99={m['tpot']['p50']:.3g}/{m['tpot']['p99']:.3g} "
            f"util={m['slot_utilization']:.2f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", default="qwen2_7b,mamba2_130m",
                    help="comma-separated registry configs (reduced)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--process", default="poisson",
                    choices=("poisson", "bursty", "uniform"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clock", default="wall", choices=("wall", "virtual"))
    ap.add_argument("--queue-limit", type=int, default=None)
    ap.add_argument("--prompt-hi", type=int, default=12)
    ap.add_argument("--out-hi", type=int, default=10)
    ap.add_argument("--no-plan", action="store_true",
                    help="skip the per-block compiler bridge")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 'data=2,model=2' (needs that many devices)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale reduced run for CI")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    if args.smoke:
        args.requests = min(args.requests, 8)
        args.slots = min(args.slots, 2)
        args.prompt_hi = min(args.prompt_hi, 7)
        args.out_hi = min(args.out_hi, 5)

    from repro.device import enable_compile_cache
    enable_compile_cache()
    mesh = parse_mesh(args.mesh)
    entries: List[Dict] = []
    for name in args.configs.split(","):
        name = name.strip()
        t0 = time.perf_counter()
        entry = run_config(
            name, slots=args.slots, requests=args.requests, rate=args.rate,
            process=args.process, seed=args.seed, clock_kind=args.clock,
            queue_limit=args.queue_limit, prompt_hi=args.prompt_hi,
            out_hi=args.out_hi, with_plan=not args.no_plan, mesh=mesh)
        entry["bench_wall_s"] = round(time.perf_counter() - t0, 3)
        entries.append(entry)
        print(fmt_entry(entry))

    doc = {"schema": "serve_bench/v1", "entries": entries}
    check_bench(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"// json written to {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
