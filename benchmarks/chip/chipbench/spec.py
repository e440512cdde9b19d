"""Finds a cell's configuration, traffic mix and metric readers by the names
``BENCHMARK.json`` gives them: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``metrics/<metric>.py`` under the benchmark's
directory. A later cell, mix or metric is new files and new entries; no
code here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    peaks: dict


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, *, bench_dir: Path = BENCH_DIR, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json ({sorted(by_name)})")
    w = by_name[workload]
    config = _json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        peaks=_json(bench_dir / "peaks.json"),
    )


def metric_reader(name: str, *, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(peaks: dict, device_kind: str) -> dict:
    """Peaks of one chip; an unknown device is an error, not a default."""
    table = peaks["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json ({sorted(table)})")
    return table[device_kind]
