"""Model FLOP/s utilisation of decoding over the chips of a
tensor-parallel layout, in % of their summed peak.

The whole model's FLOPs (``bench/work/decode_step.py``) of every decode
step the recorder saw in the traced slices, over the slices' length and
``run.model_parallel`` times one chip's peak.  The share of the whole
step: it bounds what any part of the step can claim.
"""

from bench.metrics import traced_steps
from bench.work import decode_step


def read(t, rec, peak):
    cfg = rec["config"]
    chips = int(cfg["run"].get("model_parallel", 1))
    steps = traced_steps(rec)
    span = sum(b - a for a, b in rec["slices"])
    if not steps or span <= 0:
        return None
    flops = sum(decode_step.step_flops(cfg, a, v) for _, a, v in steps)
    return 100.0 * flops / span / (chips * peak["flops_per_s"])
