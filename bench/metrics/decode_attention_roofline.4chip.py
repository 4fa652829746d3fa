"""The decode-attention kernel's share of its roofline on one chip of a
tensor-parallel layout, in %.

Each chip attends its own KV heads with their query groups: with
``run.model_parallel`` = n, a 1/n share of the heads (1 KV head and 7
query heads of Qwen2-7B over 4 chips).  Work per chip
(``bench/work/decode_attention.py`` at that share of the heads): the
live slots' valid context, averaged over the decode steps the recorder
saw in the traced slices, times the steps each chip ran.  Time: the
device time of the Mosaic kernels inside ``_batched_step`` on one chip.
``bench/trace.py`` sums runs and time over the device planes, so both
are divided by the chips.  It is bound by bandwidth.
"""

from bench.metrics import traced_steps
from bench.work import decode_attention, roofline_pct


def read(t, rec, peak):
    cfg = rec["config"]
    chips = int(cfg["run"].get("model_parallel", 1))
    steps = traced_steps(rec)
    runs, _ = t.program("_batched_step")
    _, seconds = t.kernel("_batched_step")
    if not steps or runs == 0 or seconds <= 0:
        return None
    share = dict(cfg, num_attention_heads=cfg["num_attention_heads"] // chips,
                 num_key_value_heads=cfg["num_key_value_heads"] // chips)
    work = [decode_attention.step(share, a, v) for _, a, v in steps]
    per_chip_steps = runs / chips
    flops = sum(f for f, _ in work) / len(work) * per_chip_steps
    nbytes = sum(b for _, b in work) / len(work) * per_chip_steps
    return roofline_pct(flops, nbytes, seconds / chips, peak)
