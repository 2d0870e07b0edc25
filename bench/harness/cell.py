"""Cells as data: ``BENCHMARK.json`` names a cell's configuration, traffic
mix and metrics, and each of those is a file under ``bench/`` found by its
name; a mix names its generator (``traffic/<generator>.py``) and a
configuration its system (``systems/<system>.py``, ``reference/<system>.py``)
the same way.  Adding a cell, a configuration, a traffic mix, a generator or
a per-layer metric adds files and edits none."""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric and system files have dots in their
    names, so they are not importable by name)."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    name = re.sub(r"\W", "_", name)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]     # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    metric_files: Dict[str, ModuleType]
    generator: ModuleType
    system: ModuleType
    reference: ModuleType


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or
    every cell where the metric has no such key."""
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if reports(m, name)]
    files = {m["name"]: load_module(root / "bench" / "metrics"
                                    / f"{m['name']}.py")
             for m in e2e + per_layer}
    system = config["system"]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, files,
                load_module(root / "bench" / "traffic"
                            / f"{traffic['generator']}.py"),
                load_module(root / "bench" / "systems" / f"{system}.py"),
                load_module(root / "bench" / "reference" / f"{system}.py"))
