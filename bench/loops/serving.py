"""Serving cells: the window drives ``ContinuousEngine.submit``/``step``.

Set-up makes the weights on the chip from the seed in one jitted call,
builds the engine as ``launch/serve.py --continuous`` does, and warms up
exactly the programs the window drives: one prefill per length of the
mix's prompt set, the slot write and the batched decode step.  The mix's
``arrivals`` says how requests come:

* ``backlog``: every request is queued and one step fills every slot
  before the window opens, so the window measures a full batch;
* ``poisson`` or ``bursty``, an open loop: requests are submitted at
  their due times, starting ``prelude_s`` before the window opens so
  that it opens on a system in its steady state.  When the window closes
  every request due in it has been submitted, and the engine steps on
  until each has its first token, or a minute has passed.

Then the peak of device memory is read, the engine and its caches are
freed, and a sample of the finished requests, drawn from the seed with
the longest among them, is scored against the plain reference.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import traffic as traffic_mod
from bench.cells import Cell, family_module
from bench.loops import Outcome, peak_bytes
from bench.models.common import run_key
from bench.recorder import Recorder, percentile
from repro.serve.continuous import ContinuousEngine, Request

WARM_RID = 1 << 30                  # request ids of the warm-up
HORIZON_S = 60.0                    # open-loop arrivals are drawn this far
GRACE_S = 60.0                      # wait for first tokens after the close
NO_ANSWER = 1e9                     # a compared number where none came


def _warm_up(engine: ContinuousEngine, prompt_set: List[int]) -> None:
    for i, p in enumerate(prompt_set):
        engine.submit(Request(WARM_RID + i, np.zeros(p, np.int32), 2))
        engine.drain()
    engine.results.clear()


def _sample(results: Dict[int, np.ndarray], rng: np.random.Generator,
            tokens: int, most: int) -> List[int]:
    """The request with the most served tokens, then others drawn from
    ``rng``, until ``tokens`` served tokens or ``most`` requests."""
    rids = sorted(results)
    if not rids:
        return []
    first = max(rids, key=lambda r: (len(results[r]), -r))
    out, total = [first], len(results[first])
    for r in rng.permutation(rids):
        if total >= tokens or len(out) >= most:
            break
        if r != first:
            out.append(int(r))
            total += len(results[r])
    return out


def run(cell: Cell, seed: int, seconds: float, tracer, t_process: float,
        engine_hook=None) -> Outcome:
    cfg, mix = cell.config, cell.traffic.params
    fam = family_module("models", cfg)
    model, slots, max_len = fam.build(cfg)
    params = fam.init_params(model, cfg, run_key(seed))
    jax.block_until_ready(params)

    prelude = float(mix.get("prelude_s", 0.0))
    reqs = traffic_mod.stream(mix, seed, model.cfg.vocab_size,
                              prelude + HORIZON_S)
    by_rid = {r.rid: r for r in reqs}
    rec = Recorder({r.rid: len(r.prompt) for r in reqs})
    engine = ContinuousEngine(model, params, slots=slots, max_len=max_len)
    if engine_hook is not None:
        engine_hook(engine)
    _warm_up(engine, cell.traffic.prompt_set)
    engine.metrics = rec
    notes = []

    seconds = tracer.length(seconds)
    if mix["arrivals"] == "backlog":
        for r in reqs:
            engine.submit(Request(r.rid, r.prompt, r.max_new))
        engine.step()                       # every slot filled
        with tracer.window(seconds):
            t0 = time.perf_counter()
            t1 = t0 + seconds
            while time.perf_counter() < t1:
                with tracer.span("bench.step"):
                    engine.step()
            t1 = time.perf_counter()
        attempted = sum(1 for ts in rec.tokens.values()
                        if any(t0 <= t < t1 for t in ts))
        failed = 0
        if not engine.pending:
            notes.append("WARNING: the backlog ran dry inside the window")
    else:
        stream0 = time.perf_counter()
        due = np.asarray([stream0 + r.due for r in reqs])
        late = []
        i = 0

        def offer(now, until):
            nonlocal i
            while i < len(reqs) and due[i] <= now and due[i] < until:
                with tracer.span("bench.submit"):
                    engine.submit(Request(reqs[i].rid, reqs[i].prompt,
                                          reqs[i].max_new))
                late.append(time.perf_counter() - due[i])
                i += 1

        t0 = stream0 + prelude
        while time.perf_counter() < t0:     # the prelude: traffic, no window
            offer(time.perf_counter(), t0)
            if engine.busy:
                engine.step()
            else:
                time.sleep(0.0002)
        with tracer.window(seconds):
            t0 = time.perf_counter()
            t1 = t0 + seconds
            while True:
                now = time.perf_counter()
                if now >= t1:
                    break
                offer(now, t1)
                if engine.busy:
                    with tracer.span("bench.step"):
                        engine.step()
                else:
                    with tracer.span("bench.wait"):
                        time.sleep(min(max(due[min(i, len(due) - 1)] - now,
                                           0.0), 0.0005))
            t1 = time.perf_counter()
            offer(t1, t1)                   # the last due before the close
        notes.append(f"queue at the close: {len(engine.pending)}")
        in_window = [r.rid for r in reqs if t0 <= stream0 + r.due < t1]
        end = time.perf_counter() + GRACE_S
        while engine.busy and time.perf_counter() < end and \
                any(not rec.tokens.get(rid) for rid in in_window):
            engine.step()
        attempted = len(in_window)
        # a request with no first token after the grace counts as failed,
        # and its wait so far as its TTFT
        waited = time.perf_counter()
        ttft = [(rec.tokens[rid][0] if rec.tokens.get(rid) else waited)
                - (stream0 + by_rid[rid].due) for rid in in_window]
        failed = sum(1 for rid in in_window if not rec.tokens.get(rid))
        late = np.asarray(late or [0.0]) * 1e3
        notes.append(f"generator lateness ms: p50={percentile(late, 50)!r} "
                     f"p99={percentile(late, 99)!r} max={float(late.max())!r} "
                     f"submitted={len(late)}")

    metrics = {"setup_s": t0 - t_process}
    tokens = rec.window_tokens(t0, t1)
    metrics["tokens_per_s"] = tokens / (t1 - t0)
    gaps = rec.inter_token_gaps(t0, t1)
    if len(gaps):
        metrics["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    if mix["arrivals"] != "backlog" and attempted:
        metrics["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    notes.append(f"window: tokens={tokens} gaps={len(gaps)} "
                 f"requests_attempted={attempted} failed={failed} "
                 f"finished={len(engine.results)} steps="
                 f"{sum(1 for s in rec.steps if t0 <= s[0] < t1)}")
    memory = peak_bytes()

    # ---- correctness, after the window, with the engine's state freed
    results = dict(engine.results)
    engine._stacked = engine._tok = engine._keys = None
    del engine
    gc.collect()                        # the engine's jits hold a cycle
    ref = family_module("refs", cfg)
    pick = _sample(results, np.random.default_rng([seed, 1]),
                   int(mix["check_tokens"]), int(mix["check_requests"]))
    worst, served = 0.0, 0
    t_ref = time.perf_counter()
    for rid in pick:
        out = np.asarray(results[rid], np.int32)
        seq = np.concatenate([by_rid[rid].prompt, out])
        gap, _ = ref.score(params, cfg, seq, len(out))
        worst = max(worst, float(gap.max()))
        served += len(out)
    notes.append(f"reference: requests={len(pick)} served_tokens={served} "
                 f"seconds={time.perf_counter() - t_ref!r}")
    if not pick:
        worst = NO_ANSWER
        notes.append("no request finished: nothing to compare")
    checks = [("logit_gap", worst, float(cell.limits["logit_gap"]))]

    record = {"t0": t0, "t1": t1, "steps": rec.steps, "config": cfg,
              "tokens": rec.tokens}
    return Outcome(metrics, attempted, failed, checks, memory, record, notes)
