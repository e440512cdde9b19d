"""The benchmark's measurement of one cell, as ``run.py`` prints it."""
from __future__ import annotations

import math
import shutil

from .spec import BENCH_DIR


def number(v):
    """A reading as JSON takes it: NaN (nothing compared) becomes null."""
    return None if isinstance(v, float) and math.isnan(v) else v


def compile_cache(root) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, so only a checkout's first run of a cell compiles."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(cell, seed: int, seconds: float, trace: bool, *, t_process: float, check=None) -> dict:
    """Run the cell on the devices JAX has; the result as printed."""
    import jax

    from . import harness
    from .spec import device_peaks, metric_reader

    dev = jax.devices()[0]
    peaks = device_peaks(cell.peaks, dev.device_kind) if dev.platform == "tpu" else None
    trace_dir = None
    if trace:
        trace_dir = str(BENCH_DIR / "out" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = harness.run(cell, seed, seconds, trace_dir=trace_dir, t_process=t_process, check=check)
    rec = out["record"]
    rec.peaks = peaks
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(out["devices"]),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    chk = out["check"]
    result = {"correct": chk["correct"], "attempted": chk["attempted"], "failed": chk["failed"],
              "metrics": metrics, "device": device}
    if trace:
        s = rec.trace
        device.update(busy_s=s.busy_s, window_s=s.window_s)
        top = sorted(s.module_s.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                               "idle_gaps": [[k, v] for k, v in s.idle_gaps[:10]]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = {k: {"value": number(v["value"]), "limit": v["limit"]}
                        for k, v in chk["numbers"].items()}
    return {"result": result, "info": out["info"]}
