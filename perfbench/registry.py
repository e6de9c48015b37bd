"""Finds a cell's pieces by name: every configuration, traffic mix, cell
and per-layer metric is a file of its own under ``perfbench/``, so a later
change adds files and never edits one.

    configs/<config>.json     sizes as run, source, cuts, plain reference
    traffic/<traffic>.json    the mix's parameters
    workloads/<cell>.json     config + traffic names, why, correctness limits
    metrics/<metric>.py       ``read(record) -> float | None``
    reference/<family>.py     the plain model the config names
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind} named {name!r} ({path} is missing)")
    with open(path) as f:
        return json.load(f)


def workload(name: str) -> dict:
    return _json("workloads", name)


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def reference_family(name: str):
    """The plain model module ``perfbench/reference/<name>.py``."""
    if not (HERE / "reference" / f"{name}.py").is_file():
        raise KeyError(f"no reference model named {name!r}")
    return importlib.import_module(f"perfbench.reference.{name}")


def metric(name: str):
    """The reducer module ``perfbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no metric reader named {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [e for e in bench[kind]
            if "workloads" not in e or cell in e["workloads"]]
