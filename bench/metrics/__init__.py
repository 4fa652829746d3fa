"""Per-layer metrics: ``bench/metrics/<metric>.py`` holds ``read`` (the
file of a qualified name's quantity reads it: ``idle_share.py`` reads
``idle_share.chat``).

``read(t, rec, peak)`` takes the reduced trace (``bench.trace.Reduced``)
of the run's traced slices, the run's record (``rec["config"]``, the
recorder's decode ``steps`` as (host time, live slots, valid rows), the
traced slices' host ``slices``, and for the kernel cell its
``kernels``) and the device's peaks.  It returns one number, or None
where the trace holds nothing to read: never 0 for a share of a peak.
"""

from __future__ import annotations

from typing import List, Optional, Tuple


def per_run_ms(t, programs: List[str], per: str) -> Optional[float]:
    """Device time of ``programs`` over the runs of ``per``, in ms."""
    n, _ = t.program(per)
    if n == 0:
        return None
    return 1e3 * sum(t.program(p)[1] for p in programs) / n


def traced_steps(rec) -> List[Tuple[float, int, int]]:
    """The recorder's decode steps that fall in a traced slice."""
    return [s for s in rec["steps"]
            if any(a <= s[0] < b for a, b in rec["slices"])]


def kernel_roofline(t, rec, peak, name: str) -> Optional[float]:
    """A generated kernel's work per call times its program's runs in the
    trace, over the device time of its Mosaic kernels."""
    from bench.cells import load_file_module
    from bench.work import roofline_pct
    k = next(k for k in rec["kernels"] if k.name == name)
    runs, _ = t.program(k.program)
    _, seconds = t.kernel(k.program)
    if runs == 0 or seconds <= 0:
        return None
    flops, nbytes = load_file_module("work", name).call(k.spec)
    return roofline_pct(flops * runs, nbytes * runs, seconds, peak)
