"""The benchmark's own recorder of a serving run, on the host clock.

``ContinuousEngine`` calls ``on_submit``, ``on_admit``, ``on_token``,
``on_step`` and ``on_finish``.  It calls ``on_token`` for a request's
first token right after dispatching the prefill and before the host
waits for it, so that call would stamp the enqueue.  The recorder holds
the first token back and stamps it at the next call of any hook: each of
those follows the host's wait for the first token.  Every later token is
stamped when its hook is called, after the step's result is on the host.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np


class _Clock:
    """The engine advances a virtual clock per step; the host clock
    ignores it."""

    kind = "wall"

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def advance(self, dt: float) -> None:
        pass


def percentile(values, q: float) -> float:
    """Exact percentile (linear between the two nearest ranks)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class Recorder:
    def __init__(self, prompt_len: Dict[int, int]):
        self.clock = _Clock()
        self.prompt_len = prompt_len          # rid -> prompt length
        self.admit: Dict[int, float] = {}
        self.tokens: Dict[int, List[float]] = {}
        self.steps: List[tuple] = []          # (t, active, sum of lengths)
        self.live: set = set()                # admitted, not finished
        self._held: List[int] = []

    def _flush(self) -> float:
        now = time.perf_counter()
        for rid in self._held:
            self.tokens[rid].append(now)
        self._held.clear()
        return now

    def on_submit(self, rid: int, arrival: Optional[float] = None) -> None:
        self._flush()

    def on_reject(self, rid: int) -> None:
        self._flush()

    def on_admit(self, rid: int, prompt_len: int) -> None:
        self.admit[rid] = self._flush()
        self.live.add(rid)

    def on_token(self, rid: int) -> None:
        toks = self.tokens.setdefault(rid, [])
        if not toks and rid not in self._held:
            self._flush()
            self._held.append(rid)            # the host has not waited yet
            return
        toks.append(self._flush())

    def on_step(self, queue_depth: int, active_slots: int) -> None:
        now = self._flush()
        if not self.live:
            return                            # no decode this step
        # each live slot attends its prompt and every token it emitted
        # (the newest is written to the cache by this step)
        total = sum(self.prompt_len[r] + len(self.tokens[r])
                    for r in self.live)
        self.steps.append((now, len(self.live), total))

    def on_finish(self, rid: int) -> None:
        self._flush()
        self.live.discard(rid)

    # ---- what the window saw -----------------------------------------

    def window_tokens(self, t0: float, t1: float) -> int:
        return sum(int(np.sum((np.asarray(ts) >= t0) & (np.asarray(ts) < t1)))
                   for ts in self.tokens.values())

    def inter_token_gaps(self, t0: float, t1: float) -> np.ndarray:
        """Gaps between consecutive tokens of one request, both inside
        the window, over all requests."""
        out = []
        for ts in self.tokens.values():
            a = np.asarray(ts)
            a = a[(a >= t0) & (a < t1)]
            if len(a) > 1:
                out.append(np.diff(a))
        return np.concatenate(out) if out else np.zeros(0)
