#!/usr/bin/env python3
"""Chip benchmark of EchoPFL's coalesced async loop: one run of one cell.

    python3 benchmarks/chip/run.py --workload har-256-w5 --seed 7 --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``configs/``) and a traffic mix (``traffic/``). The run builds that many
clients and the EchoPFL server from ``--seed``, warms the coalesced loop up
to the mix's virtual horizon, times ``--seconds`` of it, and compares what
the window produced with the plain reference (``chipbench/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced with the JAX profiler and the result
carries its per-layer metrics (``metrics/<name>.py``), the device's busy
and window seconds and a breakdown. Earlier lines on stderr say how set-up
split and whether anything compiled in the window; the last lines on
stderr, and the result's last key, give each compared number beside its
limit. The last line on stdout is the result, one JSON object.

Exits non-zero, and prints no result, when JAX's backend is not a TPU or
has fewer chips than the cell asks for, or when the run fails.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def _err(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench.measure import compile_cache, measure
    from chipbench.spec import load_cell

    cache = compile_cache(ROOT)  # before anything compiles
    import repro.fl.simulator  # noqa: F401  the program under test, from the checkout

    cell = load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _err(f"run.py: no TPU: JAX's backend is {dev.platform}")
        return 2
    if len(jax.devices()) < cell.chips:
        _err(f"run.py: {args.workload} needs {cell.chips} chips, JAX sees {len(jax.devices())}")
        return 2
    _err("cache", json.dumps({"compile_cache": cache}))
    out = measure(cell, args.seed, args.seconds, bool(args.trace), t_process=T_PROCESS)
    info, result = out["info"], out["result"]
    _err("setup", json.dumps(info["setup_parts_s"]))
    _err("window", json.dumps(info["window"]))
    _err("run", json.dumps({k: v for k, v in info.items() if k not in ("setup_parts_s", "window")}))
    for name, c in result["checks"].items():
        _err(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
