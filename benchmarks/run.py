"""Benchmark driver — one module per paper table/figure (DESIGN.md Sec. 7).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only name[,name]]

Writes JSON artifacts to experiments/bench/ and prints each table.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (
    bench_ablations,
    bench_accuracy_time,
    bench_async_coalesce,
    bench_client_fleet,
    bench_clustering_quality,
    bench_comm_cost,
    bench_comm_peaks,
    bench_defense,
    bench_distance_metrics,
    bench_drift_adaptation,
    bench_faults,
    bench_hm_sensitivity,
    bench_lm_fleet,
    bench_roofline,
    bench_server_throughput,
    bench_slow_device_drop,
)
from repro.common.compile_cache import use_compile_cache

BENCHES = {
    "accuracy_time": bench_accuracy_time.run,       # Tab.1 / Fig.8
    "slow_device_drop": bench_slow_device_drop.run, # Fig.2
    "comm_cost": bench_comm_cost.run,               # Fig.9 / Tab.3
    "comm_compress": bench_comm_cost.run_compress,  # REPRO_UPLINK measured sweep
    "comm_peaks": bench_comm_peaks.run,             # Fig.10
    "clustering_quality": bench_clustering_quality.run,  # Fig.11 / Fig.12
    "distance_metrics": bench_distance_metrics.run, # Tab.5
    "ablations": bench_ablations.run,               # Fig.15
    "hm_sensitivity": bench_hm_sensitivity.run,     # Fig.16
    "drift_adaptation": bench_drift_adaptation.run, # Fig.18 / Fig.19
    "roofline": bench_roofline.run,                 # deliverable (g)
    "server_throughput": bench_server_throughput.run,  # plane vs pytree hot path
    "client_fleet": bench_client_fleet.run,         # loop vs fleet client plane
    "async_coalesce": bench_async_coalesce.run,     # event-coalesced async pipeline
    "lm_fleet": bench_lm_fleet.run,                 # REPRO_TASK=lm throughput + model axis
    "faults": bench_faults.run,                     # chaos sweep: retry vs drop-straggler
    "defense": bench_defense.run,                   # poison sweep: guard off vs on
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes for smoke runs")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args()
    use_compile_cache()

    names = list(BENCHES) if not args.only else [n.strip() for n in args.only.split(",")]
    failures = []
    for name in names:
        print(f"\n{'='*72}\n[{name}]")
        t0 = time.time()
        try:
            BENCHES[name](quick=args.quick)
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    print(f"\n{'='*72}\ncompleted {len(names) - len(failures)}/{len(names)} benchmarks")
    if failures:
        raise SystemExit(f"failed: {failures}")


if __name__ == "__main__":
    main()
