"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* a configuration: the ``file`` its entry names (JSON: sizes, mapping,
  engine options), whose ``shape`` names its seeded generator in
  ``shapes/<shape>.py`` (the plain reference, ``refkg.py``, follows the
  mapping the file holds);
* a traffic mix: ``traffic/<traffic>.json``, whose ``loop`` names the
  loop in ``loops/<loop>.py`` that runs it;
* a per-layer metric: the reader ``metrics/<metric name>.py``.

Adding a cell, a mix or a metric adds files and entries; no list here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List, Mapping, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[Mapping], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return dict(e)
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have: {sorted(e['name'] for e in entries)})")


@dataclasses.dataclass
class Cell:
    """One workload with its configuration and traffic mix, resolved."""

    spec: Dict
    workload: Dict
    config: Dict
    traffic: Dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def applies(self, metric: Mapping) -> bool:
        """Whether an end-to-end metric is this cell's."""
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.spec["end_to_end"] if self.applies(m)]

    def per_layer(self) -> List[Dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def shape(self) -> ModuleType:
        return importlib.import_module(f"kgbench.shapes.{self.config['shape']}")

    def loop(self) -> ModuleType:
        return importlib.import_module(f"kgbench.loops.{self.traffic['loop']}")


def resolve(workload: str, root: str = ROOT,
            spec: Optional[Dict] = None) -> Cell:
    spec = load_spec(root) if spec is None else spec
    wl = _by_name(spec["workloads"], workload, "workload")
    cfg_entry = _by_name(spec["configs"], wl["config"], "configuration")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", wl["traffic"] + ".json"))
    return Cell(spec=spec, workload=wl, config=config, traffic=traffic)


def metric_reader(name: str) -> ModuleType:
    """The module ``metrics/<name>.py``; its ``read(run)`` returns the
    metric's value, or ``None`` where the run holds nothing to read."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "kgbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
