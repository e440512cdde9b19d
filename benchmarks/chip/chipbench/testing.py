"""Helpers for the benchmark's own CPU tests: a cell cut to a size a test
run can hold, run in-process past the harness's look for a chip."""
from __future__ import annotations

import contextlib
import json
import time

from .spec import BENCH_DIR, ROOT, load_cell

SEED = 2**33 + 5  # wider than 32 bits, as seeds may be


def small_cell(workload: str, *, traffic: str | None = None, clients: int = 64,
               horizon: float = 120.0, bench_dir=BENCH_DIR, root=ROOT):
    """``workload`` cut to ``clients``; ``traffic`` swaps in another mix from
    ``traffic/`` (a mix no cell of ``BENCHMARK.json`` uses yet). ``bench_dir``
    and ``root`` point at another copy of the benchmark."""
    cell = load_cell(workload, bench_dir=bench_dir, root=root)
    if traffic is not None:
        cell.traffic = json.loads((bench_dir / "traffic" / f"{traffic}.json").read_text())
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


@contextlib.contextmanager
def fixed_window(supersteps: int):
    """Close the window, and the rehearsal's, after ``supersteps`` window
    supersteps in place of a wall time, so that two runs of one seed ingest
    the same work and compare the same samples."""
    from .harness import Recorder

    with patched(Recorder, "_window_over", lambda _: lambda self, now: self.window_steps >= supersteps):
        yield
