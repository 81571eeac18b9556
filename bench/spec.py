"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Its configuration file is the ``file`` of the configuration entry,
its mix is ``bench/traffic/<traffic>.json`` and each per-layer metric is
read by ``bench/metrics/<metric name>.py``.  A configuration's published
``model_type`` names its architecture, ``bench/archs/<model_type>.py``.
Adding a cell, a mix, a metric or an architecture adds files and entries;
no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "traffic" / f"{name}.json"


def metric_path(name: str, root: Path = ROOT) -> Path:
    return root / "bench" / "metrics" / f"{name}.py"


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its files read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(traffic_path(w["traffic"], root).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def _load(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    return _load(metric_path(name, root), "bench_metric_").read


def arch_path(model_type: str, root: Path = ROOT) -> Path:
    return root / "bench" / "archs" / f"{model_type}.py"


def arch(conf: Dict[str, Any], root: Path = ROOT):
    """The architecture module of a configuration file,
    ``bench/archs/<model_type>.py``: its program mapping, weight layout,
    work per token and plain reference."""
    path = arch_path(conf["model_type"], root)
    if not path.exists():
        raise FileNotFoundError(f"no architecture module {path} for "
                                f"model_type {conf['model_type']!r}")
    return _load(path, "bench_arch_")
