"""The work an algorithm needs, from the math's shapes: ``bench/work/<kernel>.py``.

Counts are of what the computation needs, not of what one implementation
does, so a later change that fuses, tiles or skips work is measured
against the same count and no share of a roofline can pass 100%.
"""

from __future__ import annotations


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peak: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bandwidth) over the measured time."""
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def bound(flops: float, nbytes: float, peak: dict) -> str:
    return ("compute" if flops / peak["flops_per_s"] >=
            nbytes / peak["hbm_bytes_per_s"] else "bandwidth")
