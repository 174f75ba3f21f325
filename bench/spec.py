"""Find a cell's configuration, traffic mix and metric readers by name.

Nothing here knows any particular cell: a later change adds a
configuration, a mix or a metric by adding its file and its entry in
``BENCHMARK.json``.  ``root`` is the checkout that holds
``BENCHMARK.json`` and ``bench/``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent

__all__ = ["ROOT", "Cell", "load_cell", "load_reader", "load_readers",
           "metric_reader_path"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                  # bench/configs/<config>.json, as run
    traffic_name: str
    traffic: dict                 # bench/traffic/<traffic>.json
    end_to_end: List[dict]        # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader_path(metric: str, root: Path = ROOT) -> Path:
    """A metric's reader: ``bench/metrics/<base>.py``, where ``base`` is
    the name up to its first dot.  The suffix after the dot names the
    end-to-end metric it moves (``.qps``, ``.p95``), not how it is read,
    so ``batch_fill.qps`` and ``batch_fill.p95`` share one reader."""
    return root / "bench" / "metrics" / f"{metric.split('.', 1)[0]}.py"


def load_reader(metric: str, root: Path = ROOT
                ) -> Callable[[object], Optional[float]]:
    """``read(run) -> float | None`` of the metric's reader module."""
    path = metric_reader_path(metric, root)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def load_readers(metrics: List[dict], root: Path = ROOT
                 ) -> Dict[str, Callable]:
    return {m["name"]: load_reader(m["name"], root) for m in metrics}
