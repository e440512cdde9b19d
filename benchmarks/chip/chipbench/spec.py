"""Finds a cell's configuration, traffic mix, client model and metric
readers by the names ``BENCHMARK.json`` and the configuration give them:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``models/<client_model>.py`` and ``metrics/<metric>.py`` under the
benchmark's directory. A later cell, mix, client model or metric is new
files and new entries; no code here names one.

A client model module answers what depends on the model a client trains:

* ``check_sizes(config)`` raises where the configuration's sizes are not
  the program's;
* ``draw(config, rng, program_seed, seed)`` gives the clients' data (drawn
  from ``rng``) and the initial row pytree, both permuted by ``seed``, and the
  keyword arguments each ``SimClient`` takes besides its id, device class
  and round time;
* ``row_floats(config)`` counts the floats of a plane row;
* ``train_flops_per_upload(config, head_only)`` counts the training FLOPs
  one upload requires;
* ``train_reference(config, base, data, *, epochs, lr, head_only, cast)``
  retrains a flat row on one client's data with the plain reference the
  configuration's ``reference`` key names.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any

MODEL_FUNCTIONS = ("check_sizes", "draw", "row_floats", "train_flops_per_upload", "train_reference")

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: Any  # the configuration's client model module (models/<client_model>.py)
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
    if "client_model" not in config:
        raise KeyError(f"configuration {w['config']!r} names no client_model")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        model=client_model(config["client_model"], bench_dir=bench_dir),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        peaks=_json(bench_dir / "peaks.json"),
    )


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python file at ``path``, loaded by path, once per process."""
    name = "chipbench_file_" + "_".join(path.with_suffix("").parts[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, *, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read


def client_model(name: str, *, bench_dir: Path = BENCH_DIR):
    """The module ``models/<name>.py``; one that lacks a function of
    :data:`MODEL_FUNCTIONS` is an error."""
    mod = load_module(bench_dir / "models" / f"{name}.py")
    missing = [f for f in MODEL_FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"client model {name!r} lacks {missing}")
    return mod


def device_peaks(peaks: dict, device_kind: str) -> dict:
    """Peaks of one chip; an unknown device is an error, not a default."""
    table = peaks["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json ({sorted(table)})")
    return table[device_kind]
