"""Device time of the collectives in one batched decode step, per chip,
in ms.

The operations inside ``_batched_step`` whose kind is a collective
(``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, or the ``-start``/``-done`` of
an asynchronous one), each counted once.  The kind is the instruction's
name without XLA's number (``all-reduce.5`` is an ``all-reduce``).
``bench/trace.py`` sums operations over the device planes, so the total
is divided by the chips (the configuration's ``run.model_parallel``) and
by the steps each chip ran.
"""

import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
KINDS = frozenset(COLLECTIVES + tuple(f"{k}-{end}" for k in COLLECTIVES
                                      for end in ("start", "done")))
PROGRAM = "_batched_step"


def kind(instr: str) -> str:
    """``all-reduce-start.3`` -> ``all-reduce-start``."""
    return re.sub(r"\.\d+$", "", instr)


def read(t, rec, peak):
    chips = int(rec["config"]["run"].get("model_parallel", 1))
    runs, _ = t.program(PROGRAM)
    seconds = sum(s for name, s in t.ops.items()
                  if name.partition(":")[0] == PROGRAM
                  and kind(name.partition(":")[2]) in KINDS)
    if runs == 0 or seconds <= 0:
        return None
    steps = runs / chips
    return 1e3 * seconds / chips / steps
