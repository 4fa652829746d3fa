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
``bench/trace.py`` counts the spans (``Reduced.spans``); the per-layer
metrics ``admit_ms`` and ``host_step_ms`` read them through the two
functions here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

if __package__ in (None, ""):          # run as a script: ``bench`` importable
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench.trace import reduce_dir, reduce_file  # noqa: E402


def file_spans(path: str) -> Dict[str, List[float]]:
    """``serve.*`` name -> [count, seconds] inside the file's slice."""
    return dict(reduce_file(path).spans)


def dir_spans(log_dir: str) -> Dict[str, List[float]]:
    """Every trace file under ``log_dir`` (one per slice), summed."""
    return dict(reduce_dir(log_dir).spans)


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
