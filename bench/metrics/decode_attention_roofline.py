"""The decode-attention kernel's share of its roofline, in %.

Work (``bench/work/decode_attention.py``): the bytes and FLOPs of the
live slots' valid context, averaged over the decode steps the recorder
saw in the traced slices, times the decode steps the trace counted.
Time: the device time of the Mosaic kernels inside ``_batched_step``
(the decode kernel is the only one there).  It is bound by bandwidth.
"""

from bench.metrics import traced_steps
from bench.work import decode_attention, roofline_pct


def read(t, rec, peak):
    steps = traced_steps(rec)
    runs, _ = t.program("_batched_step")
    _, seconds = t.kernel("_batched_step")
    if not steps or runs == 0 or seconds <= 0:
        return None
    work = [decode_attention.step(rec["config"], a, v) for _, a, v in steps]
    flops = sum(f for f, _ in work) / len(work) * runs
    nbytes = sum(b for _, b in work) / len(work) * runs
    return roofline_pct(flops, nbytes, seconds, peak)
