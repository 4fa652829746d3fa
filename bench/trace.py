"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

One traced slice of a run is bracketed on the host by a span named
``bench.traced``.  From the ``.xplane.pb`` file this reads:

* the device planes (``/device:TPU:<n>``): their ``XLA Modules`` line
  (one event per program run, named ``jit_<name>(<hash>)``) and their
  ``XLA Ops`` line (one event per operation; a Mosaic kernel is an op
  whose text holds ``custom_call_target="tpu_custom_call"``);
* the host plane (``/host:CPU``): the slice's span, the benchmark's own
  spans (``bench.*``), the serving engine's (``serve.*``) and JAX's
  dispatch events, and the launch and completion events that tie the
  device's clock to the host's.

The device clock runs apart from the host's.  Each program's launch
precedes its start and its completion callback follows its end, so the
offset lies between the largest (launch - start) and the smallest
(done - end); the midpoint is used.

``reduce_file`` gives, for the slice: its length, the device's busy time
(the union of its operations, averaged over the chips), per program the
number of runs and their device time, per program the Mosaic kernels'
count and device time, the device time of each operation, and the idle
gaps attributed to what the host's main thread was doing in them, and
per ``serve.*`` span name its count and seconds (the spans that start
inside the slice, on the host line that holds it).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

SLICE_SPAN = "bench.traced"
SERVE_SPANS = "serve."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_CONTAINERS = ("while", "call", "conditional")


@dataclasses.dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0
    # program name -> [runs, device seconds]
    modules: Dict[str, List[float]] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(lambda: [0, 0.0]))
    # program name -> [Mosaic kernel calls, device seconds]
    kernels: Dict[str, List[float]] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(lambda: [0, 0.0]))
    ops: Dict[str, float] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(float))
    idle: Dict[str, float] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(float))
    # engine span name -> [count, host seconds]
    spans: Dict[str, List[float]] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(lambda: [0, 0.0]))

    def merge(self, other: "Reduced") -> "Reduced":
        self.window_s += other.window_s
        self.busy_s += other.busy_s
        for mine, theirs in ((self.modules, other.modules),
                             (self.kernels, other.kernels),
                             (self.spans, other.spans)):
            for k, (n, s) in theirs.items():
                mine[k][0] += n
                mine[k][1] += s
        for k, s in other.ops.items():
            self.ops[k] += s
        for k, s in other.idle.items():
            self.idle[k] += s
        return self

    def program(self, name: str) -> Tuple[int, float]:
        n, s = self.modules.get(name, (0, 0.0))
        return int(n), float(s)

    def kernel(self, name: str) -> Tuple[int, float]:
        n, s = self.kernels.get(name, (0, 0.0))
        return int(n), float(s)

    def breakdown(self, top: int = 10) -> Dict:
        def best(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": best(self.ops), "idle_gaps": best(self.idle)}


def program_name(module_event: str) -> str:
    """``jit__batched_step(1234)`` -> ``_batched_step``."""
    name = re.sub(r"\(\d+\)$", "", module_event)
    return name[4:] if name.startswith("jit_") else name


def op_name(op_event: str) -> str:
    """The HLO instruction's name: ``%fusion.3 = f32[...] ...`` ->
    ``fusion.3``."""
    return op_event.split(" = ", 1)[0].lstrip("%").strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _offset(modules, launches, dones) -> float:
    """Host clock minus device clock, in ns."""
    n = min(len(modules), len(launches), len(dones))
    if n == 0:
        return 0.0
    mods = modules[-n:] if len(modules) > n else modules
    lo = max(launches[-n + k] - mods[k][0] for k in range(n))
    hi = min(dones[-n + k] - mods[k][1] for k in range(n))
    return (lo + hi) / 2 if lo <= hi else hi


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    host = next(p for p in pd.planes if p.name == "/host:CPU")

    span, main, launches, dones = None, None, [], []
    for line in host.lines:
        for e in line.events:
            if e.name == SLICE_SPAN:
                span, main = (e.start_ns, e.end_ns), line
            elif e.name == "TpuLoadedExecutable::ExecuteLaunch":
                launches.append(e.start_ns)
            elif e.name == "tpu::System::Execute=>Done":
                dones.append(e.start_ns)
    if span is None:
        raise ValueError(f"{path}: no {SLICE_SPAN!r} span on the host")
    launches.sort()
    dones.sort()
    host_events = _HostEvents(sorted(
        (e.start_ns, e.end_ns, e.name) for e in main.events
        if e.name != SLICE_SPAN))

    red = Reduced(window_s=(span[1] - span[0]) * 1e-9)
    for e in main.events:
        if e.name.startswith(SERVE_SPANS) and span[0] <= e.start_ns < span[1]:
            red.spans[e.name][0] += 1
            red.spans[e.name][1] += (e.end_ns - e.start_ns) * 1e-9
    busy_total = 0.0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, program_name(e.name))
                      for e in lines["XLA Modules"].events)
        delta = _offset([(a, b) for a, b, _ in mods], launches, dones)
        s0, s1 = span[0] - delta, span[1] - delta      # device clock
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            if s0 <= a < s1:
                red.modules[name][0] += 1
                red.modules[name][1] += (b - a) * 1e-9
        busy = []
        for e in lines["XLA Ops"].events:
            a, b = e.start_ns, e.end_ns
            if b <= s0 or a >= s1:
                continue
            busy.append((max(a, s0), min(b, s1)))
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            instr = op_name(e.name)
            if KERNEL_MARK in e.name:
                red.kernels[prog][0] += 1
                red.kernels[prog][1] += (b - a) * 1e-9
            if instr.split(".")[0] not in _CONTAINERS:
                red.ops[f"{prog}:{instr}"] += (b - a) * 1e-9
        busy = _union(busy)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [s0] + [x for ab in busy for x in ab] + [s1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                red.idle[host_events.doing((g0 + g1) / 2 + delta)] += \
                    (g1 - g0) * 1e-9 / len(devices)
    red.busy_s = busy_total / max(len(devices), 1)
    return red


class _HostEvents:
    """The main thread's events, to ask what it was doing at a time."""

    def __init__(self, events):
        self.events = events
        self.starts = [e[0] for e in events]
        self.spans = [e for e in events if e[2].startswith("bench.")]
        self.span_starts = [e[0] for e in self.spans]

    def doing(self, t: float) -> str:
        """The innermost event that spans ``t``; else the benchmark's own
        span around it."""
        best = None
        i = bisect.bisect_right(self.starts, t)
        for a, b, name in self.events[max(0, i - 64):i]:
            if a <= t < b and (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        if best is None:
            j = bisect.bisect_right(self.span_starts, t) - 1
            if j >= 0 and self.spans[j][1] > t:
                best = self.spans[j]
        return best[2] if best else "host: outside any span"


def reduce_dir(log_dir: str) -> Reduced:
    """Every trace file under ``log_dir`` (one per traced slice), merged."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = Reduced()
    for f in files:
        out.merge(reduce_file(f))
    return out
