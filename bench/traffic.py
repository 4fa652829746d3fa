"""The one generator that reads every traffic mix.

A mix file (``bench/traffic/<mix>.json``) holds parameters only:

* ``loop``: the module ``bench/loops/<loop>.py`` that drives the cell,
  ``serving`` or ``kernel_suite``;
* ``arrivals`` (serving): ``backlog``, ``requests`` queued before the
  window; or an open loop, requests sent at their due times whether or
  not earlier ones finished: ``poisson`` at ``rate`` requests/s, or
  ``bursty``, a two-state Markov-modulated Poisson process whose mean is
  ``rate``, ``burst_factor`` times the calm rate inside bursts,
  ``burst_fraction`` of the time in bursts of ``burst_s`` mean length;
* ``prompt`` and ``output``: lognormal lengths (``median``, ``sigma``)
  clamped to ``[lo, hi]``; prompts are then rounded up to the next length
  of ``prompt_set`` (the largest where none is longer), so the window
  drives only programs the set-up compiled;
* ``set_size`` and ``shape_seed``: the sizes of ``set_size`` requests and
  the arrival times are drawn once, from ``shape_seed``.  The run's seed
  only orders the sizes (each block of ``set_size`` consecutive requests
  is a permutation of the same set) and draws the token ids, so every
  seed offers the same work.

Arrival times are seconds from the start of the stream.  The lognormal
lengths and the two-state arrival process follow the program's own
``repro.serve.loadgen``; this copy is the benchmark's, so no change to
the program can change the yardstick.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    due: float                      # seconds from the start of the stream
    prompt: np.ndarray              # (P,) int32
    max_new: int


def lengths(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    out = np.rint(rng.lognormal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(out.astype(np.int64), spec["lo"], spec["hi"])


def round_up(lens: np.ndarray, allowed) -> np.ndarray:
    """Each length rounded up to the next allowed one (the largest where
    none is longer)."""
    allowed = np.sort(np.asarray(allowed, np.int64))
    idx = np.minimum(np.searchsorted(allowed, lens, side="left"),
                     len(allowed) - 1)
    return allowed[idx]


def arrivals(mix: Dict, rng: np.random.Generator, horizon: float
             ) -> np.ndarray:
    """Due times in [0, horizon) seconds."""
    kind = mix["arrivals"]
    out: List[float] = []
    t = 0.0
    if kind == "poisson":
        while True:
            t += rng.exponential(1.0 / mix["rate"])
            if t >= horizon:
                return np.asarray(out)
            out.append(t)
    if kind == "bursty":
        f, k = mix["burst_fraction"], mix["burst_factor"]
        calm = mix["rate"] / (1.0 - f + f * k)        # long-run mean = rate
        burst_len = mix["burst_s"]
        calm_len = burst_len * (1.0 - f) / f
        in_burst = False
        left = rng.exponential(calm_len)
        while True:
            gap = rng.exponential(1.0 / (calm * k if in_burst else calm))
            t += gap
            left -= gap
            if left <= 0.0:
                in_burst = not in_burst
                left = rng.exponential(burst_len if in_burst else calm_len)
            if t >= horizon:
                return np.asarray(out)
            out.append(t)
    raise ValueError(f"unknown arrival process {kind!r}")


def stream(mix: Dict, seed: int, vocab: int, horizon: float) -> List[Req]:
    """The requests of one run: for open loops every arrival due in
    [0, horizon) seconds, for a backlog ``mix['requests']`` at time 0."""
    shape = np.random.default_rng(mix["shape_seed"])
    n_set = mix["set_size"]
    prompts = round_up(lengths(mix["prompt"], shape, n_set),
                       mix["prompt_set"])
    outputs = lengths(mix["output"], shape, n_set)
    if mix["arrivals"] == "backlog":
        due = np.zeros(mix["requests"])
    else:
        due = arrivals(mix, shape, horizon)
    run = np.random.default_rng(seed)
    order = np.concatenate([run.permutation(n_set)
                            for _ in range(-(-len(due) // n_set))])
    out = []
    for i, t in enumerate(due):
        j = order[i]
        toks = run.integers(0, vocab, int(prompts[j]), dtype=np.int64)
        out.append(Req(i, float(t), toks.astype(np.int32), int(outputs[j])))
    return out
