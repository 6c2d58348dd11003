"""Find a cell's parts by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, per-layer metric and driver lives in a
file of its own, and nothing here lists them:

* a configuration is the JSON file that its ``configs`` entry names;
* a traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is a reader ``bench/metrics/<metric>.py`` with a
  function ``read(run) -> float | None``.  A metric split by the
  end-to-end metric it moves in different cells (``device_idle_frac.batch``,
  ``device_idle_frac.chol``) is one quantity: without a file of its full
  name it is read by ``bench/metrics/<part before the first dot>.py``;
* a driver is ``bench/drivers/<kind>.py``, where ``kind`` is the
  configuration's ``"kind"`` key, with a function ``run(ctx)``.

A later cell, mix or metric is added by adding files and entries.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = "bench"


class CellError(ValueError):
    """A name in ``BENCHMARK.json`` resolves to nothing, or to a malformed file."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names resolved."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path

    @property
    def kind(self) -> str:
        return self.config["kind"]


def load_benchmark(root: pathlib.Path) -> Dict[str, Any]:
    path = pathlib.Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise CellError(f"no BENCHMARK.json at {root}") from e


def _read_json(path: pathlib.Path, what: str) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise CellError(f"{what}: no file {path}") from e


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def resolve(root: pathlib.Path, name: str,
            bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` of the benchmark at ``root``, with its
    configuration and traffic files read and its metrics listed."""
    root = pathlib.Path(root)
    bench = bench if bench is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; there are "
                        f"{sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names configuration "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _read_json(root / configs[w["config"]]["file"],
                        f"configuration {w['config']!r}")
    if "kind" not in config:
        raise CellError(f"configuration {w['config']!r} has no 'kind' key")
    traffic = _read_json(root / BENCH_DIR / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def _load_module(path: pathlib.Path, what: str):
    if not path.is_file():
        raise CellError(f"{what}: no file {path}")
    # the full path keys the module: two checkouts in one process (a test
    # beside the repository) never share a reader
    digest = hashlib.sha1(str(path.resolve()).encode()).hexdigest()[:12]
    stem = path.stem.replace(".", "_").replace("-", "_")
    mod_name = f"bench_file_{stem}_{digest}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def driver(cell: Cell):
    """The driver module for the cell's configuration kind."""
    return _load_module(cell.root / BENCH_DIR / "drivers" / f"{cell.kind}.py",
                        f"driver for kind {cell.kind!r}")


def metric_reader(root: pathlib.Path, metric: str):
    """``read(run)`` of the per-layer metric ``metric``."""
    folder = pathlib.Path(root) / BENCH_DIR / "metrics"
    path = folder / f"{metric}.py"
    if not path.is_file():
        path = folder / f"{metric.split('.')[0]}.py"
    module = _load_module(path, f"reader of metric {metric!r}")
    if not callable(getattr(module, "read", None)):
        raise CellError(f"metric reader {metric!r} defines no read(run)")
    return module.read
