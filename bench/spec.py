"""BENCHMARK.json and the files it names, resolved by name.

A cell names a configuration and a traffic mix; each is a JSON file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``),
and the cell has one too (``bench/cells/<cell>.json``: the plans it states
and the limits of its compared numbers). Every metric, end to end or per
layer, is a reader of its own (``bench/metrics/<name>.py`` with
``read(run) -> float | None``; a reader with nothing to read in a cell
returns None), and a configuration names its plain reference
(``bench/references/<ref>.py``). A per-layer metric lists its cells.
Adding a cell, a configuration, a traffic mix or a metric is adding files
and entries; nothing here changes. A reference states its block too
(``bench/references/decoder.py`` says what), so a configuration of
another architecture brings its own reference and the harness stays as
it is.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # bench/configs/<config>.json, with "name"
    traffic: dict  # bench/traffic/<traffic>.json, with "name"
    stated: dict  # bench/cells/<cell>.json: "plan" (, "low_plan"), "limits"
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]  # the per-layer metrics this cell reports
    reference: ModuleType  # bench/references/<config["reference"]>.py


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: Path, bench: Path = BENCH):
        self.root = Path(root)
        self.bench = Path(bench)
        self.doc = _load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.doc["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in self.doc["configs"]}
        cfg_entry = configs[w["config"]]
        config = _load_json(self.root / cfg_entry["file"])
        config.setdefault("name", cfg_entry["name"])
        traffic = _load_json(self.bench / "traffic" / f"{w['traffic']}.json")
        traffic.setdefault("name", w["traffic"])
        stated = _load_json(self.bench / "cells" / f"{name}.json")
        per_layer = [m for m in self.doc["per_layer"] if name in m["workloads"]]
        return Cell(name, int(w["chips"]), config, traffic, stated,
                    list(self.doc["end_to_end"]), per_layer, self.reference(config["reference"]))

    def reader(self, metric: str) -> ModuleType:
        return _load_module(self.bench / "metrics" / f"{metric}.py")

    def reference(self, name: str) -> ModuleType:
        return _load_module(self.bench / "references" / f"{name}.py")


def read_metrics(spec: Spec, metrics: List[dict], run) -> Dict[str, dict]:
    """Each metric's reader applied to the run; a reader that finds nothing
    to read returns None and the metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = spec.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
