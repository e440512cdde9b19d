"""Helpers for the benchmark's own CPU tests: a cell cut to a size a test
run can hold, run in-process past the harness's look for a chip."""
from __future__ import annotations

import contextlib
import json
import time

from .spec import BENCH_DIR, load_cell

SEED = 2**33 + 5  # wider than 32 bits, as seeds may be


def small_cell(workload: str, *, traffic: str | None = None, clients: int = 64,
               horizon: float = 120.0):
    """``workload`` cut to ``clients``; ``traffic`` swaps in another mix from
    ``traffic/`` (a mix no cell of ``BENCHMARK.json`` uses yet)."""
    cell = load_cell(workload)
    if traffic is not None:
        cell.traffic = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    cell.config["num_clients"] = clients
    cell.traffic["warmup_horizon_s"] = horizon
    return cell


def run_small(cell, *, seconds: float = 2.0, seed: int = SEED, control: bool = False) -> dict:
    """One run of ``cell`` on the CPU; with ``control`` also the check of
    the bfloat16 reference in the program's place, on the same samples."""
    from . import check
    from .measure import measure

    seen: dict = {}

    def both(cell, seed, rec, data, sim):
        if control:
            seen["control"] = check.check(cell, seed, rec, data, sim, control=True)
        return check.check(cell, seed, rec, data, sim)

    out = measure(cell, seed, seconds, False, t_process=time.perf_counter(), check=both)
    out["control"] = seen.get("control")
    return out


@contextlib.contextmanager
def patched(obj, name, make):
    """Replace ``obj.name`` with ``make(original)`` for the block."""
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)
