"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout's root
names the cell; its configuration file, its traffic mix
(``bench/traffic/<traffic>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``, a function ``read(run)``) are loaded from
their own files.  So are the mix's loop (``"loop": "<x>"`` in the mix:
``bench/loops/<x>.py``, functions ``driver(cell, seed)`` and
``control(cell, seed, dtype)``, the latter for ``bench/control.py``) and the
configuration's dataset (``"dataset": "<y>"``: ``bench/datasets/<y>.py``, a
function ``points(config, rng, n)``).  A later PR adds a cell, mix, metric,
loop or dataset by adding files, and edits none of these modules.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]           # entries of BENCHMARK.json, this cell's
    per_layer: List[Dict]
    readers: Dict[str, Callable]     # per-layer metric name -> read(run)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_file(path: str, prefix: str) -> ModuleType:
    """The Python file ``path``, imported as a module of its own."""
    mod_name = prefix + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(path: str) -> Callable:
    return load_file(path, "bench_metric_").read


def load_part(root: str, kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` under ``root``: a loop (``kind``
    ``loops``) or a dataset (``datasets``)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown {kind[:-1]} {name!r}: no "
                         f"{os.path.relpath(path, root)}")
    return load_file(path, f"bench_{kind}_")


def load(workload: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = _json(os.path.join(root, "bench", "traffic",
                             cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without "workloads" goes with every cell that
    # reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    readers = {m["name"]: load_reader(os.path.join(
        root, "bench", "metrics", m["name"] + ".py")) for m in per_layer}
    return Cell(root=root, name=workload, chips=int(cell["chips"]),
                config=config,
                mix=mix, end_to_end=e2e, per_layer=per_layer,
                readers=readers)
