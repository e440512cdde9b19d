#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip.

    python3 benchmarks/chip/calibrate.py --workload har-256-w5 --seconds 10 --seeds 1 2 3

For each seed, in one process (so only the first seed compiles), runs the
cell as ``run.py --trace 0`` does and prints one JSON line with the
program's readings of every compared number and the control's: the plain
reference computed in bfloat16, one precision below the float32 the
configurations state, put in the program's place on the same samples. A
limit lies above the largest program reading over a dozen seeds or more and
below the smallest control reading. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench.measure import compile_cache
    from chipbench.spec import load_cell

    compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print(f"calibrate.py: no TPU: JAX's backend is {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        cell = load_cell(args.workload)
        print(json.dumps(calibrate(cell, seed, args.seconds)), flush=True)
    return 0


def calibrate(cell, seed: int, seconds: float, *, trace: bool = False) -> dict:
    """One run of ``cell``: its result line, and the program's and the
    control's readings of each compared number."""
    from chipbench import check
    from chipbench.measure import measure, number

    seen: dict = {}

    def both(cell, seed, rec, data, sim):
        seen["control"] = check.check(cell, seed, rec, data, sim, control=True)
        return check.check(cell, seed, rec, data, sim)

    out = measure(cell, seed, seconds, trace, t_process=T_PROCESS, check=both)
    res = out["result"]
    return {
        "workload": cell.name, "seed": seed, "correct": res["correct"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "program": {k: v["value"] for k, v in res["checks"].items()},
        "control": {k: number(v["value"]) for k, v in seen["control"]["numbers"].items()},
        "control_correct": seen["control"]["correct"],
        "limits": {k: v["limit"] for k, v in res["checks"].items()},
        "info": out["info"],
    }


if __name__ == "__main__":
    sys.exit(main())
