"""Unified BENCH_*.json validator — ``make bench-check``.

Every benchmark that commits a ``BENCH_*.json`` trajectory registers its
schema here, mapped to the benchmark module that owns the matching
``check_bench(doc)`` gate.  This script loads each committed file,
dispatches on its ``schema`` field, and fails loudly on: unknown
schemas, files that no checker claims, or any gate violation (e.g. a
fabric entry whose overlap speedup slipped below the 1.3x floor).

  PYTHONPATH=src python scripts/check_bench.py [FILES...]

With no arguments, validates every BENCH_*.json in the repo root.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: schema tag -> benchmark module (relative to repo root) owning check_bench
REGISTRY = {
    "area_bench/v1": "benchmarks/area_bench.py",
    "fabric_bench/v1": "benchmarks/fabric_bench.py",
}


def _load_checker(rel: str):
    path = ROOT / rel
    spec = importlib.util.spec_from_file_location(
        pathlib.Path(rel).stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.check_bench


def check_file(path: pathlib.Path) -> str:
    doc = json.loads(path.read_text())
    schema = doc.get("schema")
    if schema not in REGISTRY:
        raise ValueError(
            f"{path.name}: schema {schema!r} not in the registry "
            f"({', '.join(sorted(REGISTRY))}) — register it in "
            f"scripts/check_bench.py")
    _load_checker(REGISTRY[schema])(doc)
    n = len(doc.get("entries", []))
    return f"{path.name}: {schema} ok ({n} entries)"


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    paths = ([pathlib.Path(a) for a in args]
             if args else sorted(ROOT.glob("BENCH_*.json")))
    if not paths:
        print("check_bench: no BENCH_*.json files found", file=sys.stderr)
        return 1
    failed = False
    for p in paths:
        try:
            print(check_file(p))
        except Exception as exc:  # noqa: BLE001 - report every file
            print(f"{p.name}: FAIL: {exc}", file=sys.stderr)
            failed = True
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
