"""Model FLOP/s utilisation of the mamba2 decode step, in % of the
chip's peak: the model FLOPs (``bench/work/mamba2_step.py``) of every
decode step the recorder saw in the traced slices, over the slices'
length and the chip's peak.  The whole step's share: it bounds what any
part of the step can claim."""

from bench.metrics import traced_steps
from bench.work import mamba2_step


def read(t, rec, peak):
    steps = traced_steps(rec)
    span = sum(b - a for a, b in rec["slices"])
    if not steps or span <= 0:
        return None
    flops = sum(mamba2_step.step_flops(rec["config"], a) for _, a, _ in steps)
    return 100.0 * flops / span / peak["flops_per_s"]
