"""Serving launcher: batched generation with prefill/decode steps.

Example (CPU-runnable):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --batch 4 --prompt-len 16 --gen 32

``--continuous`` serves a deterministic load-generator stream through
the batched continuous engine instead (one vmap'd decode step across
all slots; see ``repro.serve.continuous``) and prints the latency
metrics snapshot:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --continuous --slots 4 --requests 16 --rate 4

``--model-parallel N`` serves one replica over N devices, its layers
split tensor-parallel on a ``(data=1, model=N)`` mesh
(``RunConfig.model_parallel``); the summary line then gives the layout
and the bytes of parameters and cache that each device holds.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.device import enable_compile_cache
from repro.models.model import Model, RunConfig
from repro.serve.engine import Engine, EngineConfig, throughput_stats


def _serve_continuous(cfg, model, params, args) -> None:
    from repro.serve import loadgen
    from repro.serve.continuous import ContinuousEngine, Request
    from repro.serve.metrics import ServeMetrics, WallClock

    load = loadgen.LoadConfig(
        num_requests=args.requests, vocab_size=cfg.vocab_size,
        seed=args.seed, rate=args.rate,
        prompt=loadgen.LengthDist("uniform", 4, args.prompt_len),
        output=loadgen.LengthDist("uniform", 2, args.gen))
    metrics = ServeMetrics(WallClock(), slots=args.slots)
    engine = ContinuousEngine(model, params, slots=args.slots,
                              max_len=args.prompt_len + args.gen + 1,
                              temperature=args.temperature, seed=args.seed,
                              queue_limit=args.queue_limit, metrics=metrics)
    for r in loadgen.generate_stream(load):
        while not engine.submit(Request(r.rid, r.prompt, r.max_new)):
            engine.step()                    # backpressure: drain a step
    engine.drain()
    snap = metrics.snapshot()
    print(f"[serve] continuous: {snap['requests']['completed']} requests, "
          f"{snap['tokens']['decode']} tokens, "
          f"{snap['tokens_per_s']:.1f} tok/s, "
          f"ttft p50={snap['ttft']['p50']*1e3:.1f}ms "
          f"p99={snap['ttft']['p99']*1e3:.1f}ms "
          f"prefill_compiles={engine.prefill_compiles} "
          f"inplace_cache_leaves={engine.inplace_cache_leaves}/"
          f"{engine.cache_leaves} "
          f"layout=data1xmodel{model.run.model_parallel} "
          f"param_bytes_per_chip={engine.param_bytes_per_chip} "
          f"cache_bytes_per_chip={engine.cache_bytes_per_chip}")
    print(json.dumps(snap, indent=2, sort_keys=True))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve a load-generator stream through the "
                         "batched continuous engine")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--queue-limit", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="devices one replica spans, tensor-parallel "
                         "(data=1, model=N)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    max_len = args.prompt_len + args.gen + 1
    model = Model(cfg, RunConfig(max_seq=max_len,
                                 model_parallel=args.model_parallel))
    params = model.init(jax.random.PRNGKey(args.seed))
    print(f"[serve] arch={cfg.name} params={model.param_count():,}")

    if args.continuous:
        _serve_continuous(cfg, model, params, args)
        return

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    ee = None
    if cfg.frontend == "image_patches":
        ee = 0.1 * np.ones((args.batch, cfg.frontend_len, cfg.d_model),
                           np.float32)
    if cfg.frontend == "audio_frames":
        ee = 0.1 * np.ones((args.batch, cfg.encoder.context,
                            cfg.encoder.d_model or cfg.d_model), np.float32)

    eng = Engine(model, params, EngineConfig(max_len=max_len,
                                             temperature=args.temperature,
                                             seed=args.seed))
    if ee is not None:
        out = eng.generate(prompts, args.gen, extra_embeds=jax.numpy.asarray(ee))
        print(f"[serve] generated {out.shape} tokens")
    else:
        stats = throughput_stats(eng, prompts, args.gen)
        print(f"[serve] {stats['tokens']} new tokens in {stats['wall_s']:.2f}s "
              f"= {stats['tok_per_s']:.1f} tok/s")


if __name__ == "__main__":
    main()
