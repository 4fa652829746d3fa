"""Loop kinds: ``bench/loops/<loop>.py`` drives one kind of traffic.

Each module has ``run(cell, seed, seconds, tracer, t_process) ->
Outcome``; the mix file's ``loop`` key names the module.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple


@dataclasses.dataclass
class Outcome:
    metrics: Dict[str, float]           # end-to-end, by name
    attempted: int
    failed: int
    checks: List[Tuple[str, float, float]]   # (name, value, limit)
    memory_peak_bytes: int
    record: Dict                        # what per-layer readers may read
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks) and \
            bool(self.checks)


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())

