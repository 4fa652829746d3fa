"""Traced windows of a run, for ``--trace 1``.

A traced run measures a shorter window: ``length(seconds)`` caps it at
``slice_s``, so the profiler's session covers the whole window and is
stopped only after the window has closed (stopping it takes seconds,
which would otherwise stall the traffic inside the window).  With
tracing on, ``window(seconds)`` starts a profiler session with the
Python tracer off and opens the ``bench.traced`` span; the context's
exit ends both.  ``span(name)`` marks what the host does inside a
traced window.  With tracing off every call does nothing.  The trace
files go to a temporary directory that ``close`` removes.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from typing import List, Tuple

from bench.trace import SLICE_SPAN, Reduced, reduce_dir


class Tracer:
    def __init__(self, enabled: bool, slice_s: float):
        self.enabled = enabled
        self.slice_s = slice_s
        self.slices: List[Tuple[float, float]] = []    # host perf_counter
        self._dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled \
            else None
        self._on = False
        self.in_window = False

    def length(self, seconds: float) -> float:
        """The window a run measures: ``seconds``, or at most ``slice_s``
        where it is traced."""
        return min(seconds, self.slice_s) if self.enabled else seconds

    @contextlib.contextmanager
    def window(self, seconds: float):
        """Marks the measured window; ``seconds`` must not exceed
        ``length(seconds)``."""
        self.in_window = True
        try:
            if self.enabled:
                assert seconds <= self.slice_s, (seconds, self.slice_s)
                with self._traced():
                    yield
            else:
                yield
        finally:
            self.in_window = False

    @contextlib.contextmanager
    def _traced(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(f"{self._dir}/s{len(self.slices)}",
                                 profiler_options=opts)
        start = time.perf_counter()
        self._on = True
        try:
            with jax.profiler.TraceAnnotation(SLICE_SPAN):
                yield
        finally:
            self._on = False
            self.slices.append((start, time.perf_counter()))
            jax.profiler.stop_trace()

    def span(self, name: str):
        if not self._on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> Reduced:
        return reduce_dir(self._dir)

    def keep(self, dest: str) -> None:
        shutil.copytree(self._dir, dest, dirs_exist_ok=True)

    def close(self) -> None:
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
