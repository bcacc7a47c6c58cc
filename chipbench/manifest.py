"""`BENCHMARK.json` and the files it names.

The harness knows no cell, configuration, traffic mix or metric by
name.  It finds each by the name in the manifest:

  configuration  the `file` of its `configs` entry (JSON);
  traffic mix    `chipbench/traffic/<traffic>.json`;
  metric         `chipbench/metrics/<name>.py`, a reader with
                 `read(ctx) -> float | None` (see `harness.RunContext`).

So a later change adds a cell, a deployment, a mix or a metric by adding
files and manifest entries, and edits none of these modules."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench.traffic import load_traffic


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: Callable
    bound: Optional[float] = None
    layer: Optional[str] = None
    moves: Optional[str] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_reader(root: Path, name: str) -> Callable:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(root: Path, entries: List[Dict], cell: str) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(name=m["name"], unit=m["unit"], better=m["better"],
                          source=m["source"], reader=load_reader(root, m["name"]),
                          bound=m.get("bound"), layer=m.get("layer"),
                          moves=m.get("moves")))
    return out


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {', '.join(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = load_traffic(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_metrics(root, manifest["end_to_end"], name),
                per_layer=_metrics(root, manifest["per_layer"], name))
