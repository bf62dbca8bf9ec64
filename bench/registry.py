"""Finds what belongs to one cell by name: its entry in BENCHMARK.json,
its configuration, its traffic mix, its limits and the readers of its
per-layer metrics. Each lives in a file of its own, so a new cell,
configuration, mix or metric is added as files and entries only."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _named(kind: str, name: str, path: Path) -> Path:
    if not NAME.match(name) or not path.is_file():
        raise KeyError(f"unknown {kind} {name!r} "
                       f"(no {path.relative_to(ROOT)})")
    return path


def _json(kind: str, name: str, sub: str) -> dict:
    return json.loads(_named(kind, name, BENCH / sub / f"{name}.json")
                      .read_text())


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, bench: dict) -> dict:
    """The `workloads` entry of cell `name`."""
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"unknown workload {name!r}")


def config(name: str) -> dict:
    return _json("configuration", name, "configs")


def traffic(name: str) -> dict:
    return _json("traffic mix", name, "traffic")


def reference(metric: str) -> ModuleType:
    """The plain reference for a configuration's distance metric."""
    return _module("reference", metric, BENCH / "references")


def layer_metric(name: str) -> ModuleType:
    """The reader of per-layer metric `name`: `read(run) -> float | None`."""
    return _module("per-layer metric", name, BENCH / "layer_metrics")


def _module(kind: str, name: str, where: Path) -> ModuleType:
    path = _named(kind, name, where / f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{where.name}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(cell_name: str, bench: dict, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries that cell `cell_name`
    reports: those that list it, or that list no cells and move (or, for
    end-to-end metrics, are) a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in names]
