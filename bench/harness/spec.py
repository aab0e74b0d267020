"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics.  Each piece lives in a file of its own:

* ``configs[i]["file"]``: the configuration (sizes, deployment, limits);
* ``<root>/traffic/<traffic>.json``: the traffic mix;
* ``<root>/metrics/<metric name>.py``: the metric's reader, a module with
  ``read(run) -> float | None``.  A name ``<base>.<variant>`` (one
  quantity split by the end-to-end metric it moves) falls back to
  ``<base>.py`` when it has no file of its own.

So a later change adds a cell, a mix or a metric with new files and new
entries, and edits no file that is there."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]        # metrics this cell reports, trace 0
    per_layer: List[dict]         # metrics this cell reports, trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str,
              bench_root: str = BENCH_ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, cfgs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_root, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)])


def reader(name: str, bench_root: str = BENCH_ROOT) -> Callable:
    """The ``read`` function of ``<bench_root>/metrics/<name>.py``, or of
    ``<base>.py`` for a ``<base>.<variant>`` name without a file."""
    path = os.path.join(bench_root, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(bench_root, "metrics",
                            name.split(".", 1)[0] + ".py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], run, bench_root: str = BENCH_ROOT
                 ) -> Dict[str, dict]:
    """Every metric whose reader finds something to read, with its unit."""
    out: Dict[str, dict] = {}
    for m in metrics:
        v = reader(m["name"], bench_root)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
