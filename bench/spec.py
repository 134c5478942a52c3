"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: it names a configuration (whose
``file`` holds the sizes) and a traffic mix (``traffic/<name>.json``).
Its metrics are the ``end_to_end`` and ``per_layer`` entries whose
``workloads`` list names the cell, or that have no such list.  A
per-layer metric is read by ``metrics/<name>.py``, and what belongs to
the configuration's architecture by ``arch/<architectures[0]>.py``.
Adding a configuration (of a new architecture too), a mix or a metric
is adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def read_json(path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: pathlib.Path = REPO) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read."""
    root = pathlib.Path(root)
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=workload,
        config_name=w["config"],
        config=read_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=read_json(root / BENCH.name / "traffic"
                          / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The module ``<bench>/metrics/<name>.py``; its ``read(ctx)`` returns
    the metric's value, or None when the run holds nothing to read."""
    path = pathlib.Path(bench) / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
