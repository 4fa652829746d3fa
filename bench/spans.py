#!/usr/bin/env python3
"""The serving engine's own spans in the trace of a traced run.

``ContinuousEngine`` marks its host work on the profiler's clock with
``serve.*`` spans (``repro/serve/continuous.py``): ``serve.step`` around
a step, and in it ``serve.admit`` (with ``serve.prefill`` or
``serve.prefill.compile``, ``serve.first_token`` and ``serve.write``),
``serve.decode``, ``serve.sync`` and ``serve.emit``.  This reads them
from the trace files that ``bench/run.py --trace 1 --keep-trace DIR``
keeps, and prints one JSON object:

    python3 bench/spans.py DIR

It holds, over every traced slice (the host's ``bench.traced`` span),
each span name's count and seconds (``spans``: the ``serve.*`` events
that start inside the slice, on the host line that holds it), and two
readings of the scheduler:

* ``admit_ms``: the mean length of ``serve.admit``, in ms per admission:
  the prefill's device time and the host's dispatch and wait;
* ``host_step_ms``: the length of ``serve.step`` less its ``serve.sync``
  and ``serve.first_token``, in ms per step: the host's own work in a
  step, while the device waits for it where requests are queued.

Each reading is None where the trace holds none of the spans it reads.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
from typing import Dict, List, Optional

if __package__ in (None, ""):          # run as a script: ``bench`` importable
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench.trace import SLICE_SPAN  # noqa: E402

PREFIX = "serve."


def file_spans(path: str) -> Dict[str, List[float]]:
    """``serve.*`` name -> [count, seconds] inside the file's slice."""
    from jax.profiler import ProfileData
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    line = next((ln for ln in host.lines
                 if any(e.name == SLICE_SPAN for e in ln.events)), None)
    if line is None:
        raise ValueError(f"{path}: no {SLICE_SPAN!r} span on the host")
    events = list(line.events)
    window = next((e.start_ns, e.end_ns) for e in events
                  if e.name == SLICE_SPAN)
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for e in events:
        if e.name.startswith(PREFIX) and window[0] <= e.start_ns < window[1]:
            out[e.name][0] += 1
            out[e.name][1] += (e.end_ns - e.start_ns) * 1e-9
    return dict(out)


def dir_spans(log_dir: str) -> Dict[str, List[float]]:
    """Every trace file under ``log_dir`` (one per slice), summed."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for f in files:
        for name, (n, s) in file_spans(f).items():
            out[name][0] += n
            out[name][1] += s
    return dict(out)


def admit_ms(spans) -> Optional[float]:
    n, s = spans.get("serve.admit", (0, 0.0))
    return 1e3 * s / n if n else None


def host_step_ms(spans) -> Optional[float]:
    n, s = spans.get("serve.step", (0, 0.0))
    if not n or "serve.sync" not in spans:
        return None
    waits = sum(spans.get(w, (0, 0.0))[1]
                for w in ("serve.sync", "serve.first_token"))
    return 1e3 * (s - waits) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log_dir", help="the directory of --keep-trace")
    spans = dir_spans(ap.parse_args(argv).log_dir)
    print(json.dumps({"spans": spans, "admit_ms": admit_ms(spans),
                      "host_step_ms": host_step_ms(spans)}, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
