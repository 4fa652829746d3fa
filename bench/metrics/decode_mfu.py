"""Model FLOP/s utilisation of decoding, in % of the chip's peak.

The model FLOPs (``bench/work/decode_step.py``) of every decode step the
recorder saw in the traced slices, over the slices' length and the
chip's peak.  Decoding is bound by bandwidth, so this stays small; it
bounds what any kernel of the decode step can claim.
"""

from bench.metrics import traced_steps
from bench.work import decode_step


def read(t, rec, peak):
    steps = traced_steps(rec)
    span = sum(b - a for a, b in rec["slices"])
    if not steps or span <= 0:
        return None
    flops = sum(decode_step.step_flops(rec["config"], a, v)
                for _, a, v in steps)
    return 100.0 * flops / span / peak["flops_per_s"]
