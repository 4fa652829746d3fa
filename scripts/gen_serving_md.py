#!/usr/bin/env python
"""Generate docs/SERVING.md — the serving-under-load subsystem guide.

Every transcript is produced by actually running the load generator
and the per-block compile plan in-process, so the document cannot drift
from the runtime's real behaviour: CI regenerates it and fails on any diff (same contract
as docs/RAISING.md / docs/DSE.md / docs/REWRITE.md).

    PYTHONPATH=src python scripts/gen_serving_md.py > docs/SERVING.md
    # or: make docs
"""

from __future__ import annotations

import sys

from repro.serve import loadgen
from repro.serve.compiled import plan_blocks


def loadgen_transcript() -> str:
    cfg = loadgen.LoadConfig(
        num_requests=6, vocab_size=151936, seed=0, process="bursty",
        rate=4.0, burst_rate=32.0,
        prompt=loadgen.LengthDist("uniform", 4, 10),
        output=loadgen.LengthDist("uniform", 2, 6))
    stream = loadgen.generate_stream(cfg)
    lines = [f"// {cfg.describe()}"]
    for r in stream:
        lines.append(f"// rid={r.rid} arrival={r.arrival:.3f}s "
                     f"prompt_len={len(r.prompt)} max_new={r.max_new}")
    lines.append(f"// digest={loadgen.stream_digest(stream)}")
    return "\n".join(lines)


def plan_transcript() -> str:
    return plan_blocks("qwen2_7b").describe()


def main() -> int:
    lg = loadgen_transcript()
    plan = plan_transcript()

    print(f"""\
# Serving under load

<!-- GENERATED FILE — do not edit.  Regenerate with `make docs`
     (scripts/gen_serving_md.py); CI diffs this against live output. -->

The paper measures its compiler by what the generated designs do under
real workloads; this repo's equivalent is `repro.serve`: a
serving-under-load subsystem that drives the model registry's reduced
configs with deterministic request streams, batches decode across
requests in ONE jit'd step, and records tail latency per request.  Its
speed on the chip is measured by the on-chip benchmark
(`BENCHMARK.json`, `bench/run.py`), whose runs are recorded in
`PERF_LEDGER.jsonl`.

Four layers, each usable alone:

| module | role |
|---|---|
| `repro.serve.loadgen` | replayable workload generator: Poisson/bursty/uniform arrivals, configurable prompt/output length distributions |
| `repro.serve.continuous` | the batched continuous engine: slot-stacked caches, one vmap'd decode step, async admission queue with backpressure |
| `repro.serve.metrics` | per-request TTFT / TPOT / e2e in streaming log-bucket histograms, queue depth and slot occupancy per step |
| `repro.serve.compiled` | the compiler bridge: per-block compile plan (autotuned schedules, validated, explicit fallbacks) |

`repro.serve.engine` keeps the plain batched `Engine` (prefill +
decode over a fixed batch, EOS rows frozen to `eos_id`) and
`SerialSlotEngine`, the original per-slot B=1 continuous loop retained
as the bit-exact differential reference for the batched engine
(`tests/test_continuous_batching.py` asserts identical greedy token
streams on mixed request sets, including `max_new=1`).

## The load generator

A stream is a pure function of its `LoadConfig` — same seed, same
stream, byte for byte (`stream_digest` fingerprints it).  Bursty
arrivals are a two-state MMPP: a base-rate phase and a burst-rate
phase, so queueing behaviour under bursts is reproducible.

```
{lg}
```

## The batched continuous engine

`ContinuousEngine` holds ONE stacked cache pytree: each slot's rows are
exactly `model.cache_init(1, max_len)` stacked on a leading slot axis,
so per-slot scalar cache lengths survive and every slot decodes
identically to a B=1 engine — `jax.vmap` over the slot axis turns the
old per-slot Python loop (``slots`` XLA dispatches per token) into one
jit'd dispatch per token for the whole batch.  Admission prefills a
request at B=1, samples its first token, and writes the prefilled cache
into a free slot's rows with `dynamic_update_index_in_dim`; an
active-slot mask freezes empty slots.  `submit()` enqueues (with
backpressure once `queue_limit` is hit), `step()` admits + decodes one
token for every occupied slot, `drain()` runs to completion.

A request with `max_new=1` finishes at admission — the prefill already
sampled its only token, so it never occupies a slot (the off-by-one the
serial engine used to have).

## Latency metrics

`ServeMetrics` hooks the request lifecycle (submit -> admit -> first
token -> per-token -> finish) into `StreamingHistogram`s: log-spaced
buckets at 2% growth, so p50/p90/p99 are recovered within ~2% at O(1)
memory.  TTFT is measured from *arrival* (queueing included), TPOT is
the gap between consecutive decode tokens, queue depth and slot
occupancy are sampled once per engine step.  Time comes from a `Clock`:
`WallClock` for real runs, `VirtualClock` (time moves only when the
caller advances it) for tests.

## The compiler bridge

`plan_blocks(config)` raises every forward-pass block
(`repro.core.raise`), compiles each raisable one through the
PassManager stack under the autotuner's schedule for its dominant
matmul shape (falling back through `tpu_mxu` to the always-legal
nested schedule), validates against the traced reference on real
inputs, and records explicit plain-jit fallbacks with reasons — the
plan states exactly which blocks of the serving model compile through
the stack:

```
{plan}
```

## API

```python
from repro.serve import loadgen
from repro.serve.continuous import ContinuousEngine, Request
from repro.serve.metrics import ServeMetrics, WallClock

stream = loadgen.generate_stream(loadgen.LoadConfig(num_requests=32))
metrics = ServeMetrics(WallClock(), slots=4)
engine = ContinuousEngine(model, params, slots=4, max_len=256,
                          queue_limit=16, metrics=metrics)
for r in stream:
    while not engine.submit(Request(r.rid, r.prompt, r.max_new)):
        engine.step()                       # backpressure
engine.drain()
print(metrics.snapshot())
```

Or from the launcher:

```sh
PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \\
    --continuous --slots 4 --requests 16 --rate 4
```""")
    return 0


if __name__ == "__main__":
    sys.exit(main())
