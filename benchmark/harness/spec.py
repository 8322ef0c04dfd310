"""Finds a cell's files by the names in ``BENCHMARK.json``.

Layout under the checkout's root (``paths[0]`` is the benchmark's directory):

    configs/<config>.json         sizes as run; ``module`` names the builder
    configs/<module>.py           build / make_data / work / tree mapping
    references/<module>.py        the plain reference
    traffic/<traffic>.json        the mix's parameters; ``driver`` names the window
    limits/<cell>.json            each compared number's limit, with its readings
    layer_metrics/<metric>.py     read(run) -> value or None
    end_to_end/<metric>.py        read(run) -> value
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Any, Dict, List


def load_module(path: str):
    """Import a file by path; its name may hold ``-`` and ``.``."""
    name = "_bench_" + re.sub(r"\W", "_", os.path.relpath(path, "/"))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    root: str                   # the checkout
    bench_dir: str              # root/<paths[0]>
    config_name: str
    config: Dict[str, Any]
    config_mod: Any
    reference: Any
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def layer_metric_reader(self, metric: str):
        return load_module(os.path.join(
            self.bench_dir, "layer_metrics", metric + ".py")).read

    def end_to_end_reader(self, metric: str):
        return load_module(os.path.join(
            self.bench_dir, "end_to_end", metric + ".py")).read

    def driver(self):
        return importlib.import_module(
            f"{__package__}.drivers.{self.traffic['driver']}")


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, bench["paths"][0])
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _json(os.path.join(root, cfg_entry["file"]))
    module = config.get("module", cfg_entry["name"])
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    names = {m["name"] for m in e2e}
    return Cell(
        name=workload, chips=int(entry["chips"]), root=root,
        bench_dir=bench_dir,
        config_name=cfg_entry["name"], config=config,
        config_mod=load_module(os.path.join(bench_dir, "configs",
                                            module + ".py")),
        reference=load_module(os.path.join(bench_dir, "references",
                                           module + ".py")),
        traffic_name=entry["traffic"],
        traffic=_json(os.path.join(bench_dir, "traffic",
                                   entry["traffic"] + ".json")),
        limits=_json(os.path.join(bench_dir, "limits", workload + ".json")),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _in_cell(m, workload) and m["moves"] in names])
