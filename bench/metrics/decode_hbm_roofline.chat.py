"""The mamba2 decode step's share of its roofline, in %.

Work (``bench/work/mamba2_step.py``): the weights read once and each
live slot's SSM and convolution state read and written, averaged over
the decode steps the recorder saw in the traced slices, times the runs
of ``_batched_step`` the trace counted.  Time: those runs' device time.
Live slots are counted, not all slots, so a step that skips idle slots
does less work rather than reading more bytes a second.  It is bound by
bandwidth.
"""

from bench.metrics import traced_steps
from bench.work import mamba2_step, roofline_pct


def read(t, rec, peak):
    steps = traced_steps(rec)
    runs, seconds = t.program("_batched_step")
    if not steps or runs == 0 or seconds <= 0:
        return None
    cfg = rec["config"]
    flops = sum(mamba2_step.step_flops(cfg, a) for _, a, _ in steps)
    nbytes = sum(mamba2_step.step_bytes(cfg, a) for _, a, _ in steps)
    scale = runs / len(steps)
    return roofline_pct(flops * scale, nbytes * scale, seconds, peak)
