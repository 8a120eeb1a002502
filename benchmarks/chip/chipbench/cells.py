"""Find a cell's pieces by the names that ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration file, a traffic
file under ``traffic/`` whose ``job`` names the module under
``chipbench/jobs/`` that drives it, and the metrics that the cell
reports.  Each per-layer metric is read by the file
``metrics/<name>.py``.  Adding a cell, a configuration, a traffic mix
or a metric is adding files and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

# <checkout>/benchmarks/chip/chipbench/cells.py
BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


class CellError(LookupError):
    """The cell, or a piece of it, is not where its name says."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def job(self) -> str:
        return self.traffic["job"]


def _one(entries, name: str, what: str) -> Dict[str, Any]:
    found = [e for e in entries if e.get("name") == name]
    if len(found) != 1:
        raise CellError(f"{what} {name!r}: {len(found)} entries in "
                        f"BENCHMARK.json")
    return found[0]


def _read_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: Optional[Path] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench_file = bench_file or (bench_dir.parents[1] / "BENCHMARK.json")
    bench = _read_json(Path(bench_file))
    w = _one(bench["workloads"], name, "workload")
    c = _one(bench["configs"], w["config"], "config")
    root = Path(bench_file).resolve().parent
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config_name=c["name"],
                traffic_name=w["traffic"],
                config=_read_json(root / c["file"]),
                traffic=_read_json(bench_dir / "traffic"
                                   / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def job_module(cell: Cell):
    """The module that drives the cell's traffic: ``chipbench.jobs.<job>``."""
    try:
        return importlib.import_module(f"chipbench.jobs.{cell.job}")
    except ModuleNotFoundError as e:
        raise CellError(f"traffic {cell.traffic_name!r} names job "
                        f"{cell.job!r}, which has no module") from e


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` from ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
