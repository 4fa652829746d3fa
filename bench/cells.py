"""Find a cell's files by name and build what they describe.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Each is a data file found by its name alone:

* ``bench/configs/<config>.json``: the sizes as run, the source, what was
  reduced and assumed, dtypes, backend, slots and cache depth, and the
  ``family`` that names ``bench/models/<family>.py`` (builds the system
  under test and its weights) and ``bench/refs/<family>.py`` (the plain
  reference);
* ``bench/traffic/<mix>.json``: the loop that drives it
  (``bench/loops/<loop>.py``) and its parameters;
* ``bench/limits/<cell>.json``: the limit of each number compared;
* ``bench/metrics/<metric>.py`` and ``bench/work/<kernel>.py``: readers
  and work counts.

A metric's name may carry a qualifier after its first dot, where one
quantity moves different end-to-end metrics in different cells
(``idle_share.chat``, ``itl_p95_ms.chat``): the quantity is the part
before the dot, and ``bench/metrics/<quantity>.py`` reads it unless a
file of the full name exists.

Adding a cell, a mix, a configuration or a metric adds files and edits
none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(path: pathlib.Path = ROOT / "BENCHMARK.json") -> Dict:
    return json.loads(path.read_text())


def _json(kind: str, name: str) -> Dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file named {name!r}: {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    loop: str                       # bench/loops/<loop>.py
    params: Dict[str, Any]

    @property
    def prompt_set(self) -> List[int]:
        return sorted(self.params.get("prompt_set", []))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Traffic
    chips: int
    limits: Dict[str, float]
    end_to_end: List[Dict]          # the metrics this cell reports
    per_layer: List[Dict]


def load_cell(name: str, bench: Dict) -> Cell:
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[x['name'] for x in bench['workloads']]}")
    config = _json("configs", w["config"])
    t = _json("traffic", w["traffic"])
    traffic = Traffic(w["traffic"], t["loop"], t)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    limits = _json("limits", name)
    return Cell(name, config, traffic, int(w["chips"]), limits, e2e,
                per_layer)


def family_module(kind: str, config: Dict):
    """``bench/models/<family>.py`` or ``bench/refs/<family>.py``."""
    return importlib.import_module(f"bench.{kind}.{config['family']}")


def quantity(name: str) -> str:
    """A metric's name without its qualifier: ``idle_share.chat`` ->
    ``idle_share``."""
    return name.split(".", 1)[0]


def load_file_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` by path (metric names hold dots), else
    the file of the name's quantity."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        path = BENCH_DIR / kind / f"{quantity(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} reader named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

