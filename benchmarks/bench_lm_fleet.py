"""LM-fleet benchmark: personalized-LM fine-tuning throughput as plane rows.

Two legs:

  * fleet scaling — ``run_lm_experiment`` sync rounds at growing fleet
    sizes, reporting uploads/sec through the simulator and trained
    tokens/sec through the vmapped LoRA-delta launches (the whole
    cohort's transformer fwd+bwd epochs are one fused scan launch),
  * model-axis plane ops — the server-side kernels at the LM delta row
    width, single-device vs an R×M ``(plane, model)`` mesh with
    ``REPRO_PLANE_MODEL_COMPUTE`` on and off, over the devices this process
    holds (on the CPU backend, a child with a forced 8-device host).

``--json`` writes BENCH_lm_fleet.json at the repo root so the perf
trajectory is tracked across PRs.

Usage:
    python benchmarks/bench_lm_fleet.py [--sizes 8,16,32] [--rounds 2] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for p in (os.path.join(REPO_ROOT, "src"), REPO_ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.common import save_result, table  # noqa: E402

SEQ_LEN = 32
N_TRAIN = 8
LOCAL_EPOCHS = 2


def bench_fleet_scaling(sizes: tuple[int, ...], rounds: int) -> list[dict]:
    import jax

    from repro.fl.lm_task import default_lm_task, run_lm_experiment
    from repro.fl.simulator import model_bytes

    task = default_lm_task()
    delta_bytes = model_bytes(task.init_params(jax.random.PRNGKey(0)))
    kw = dict(seq_len=SEQ_LEN, n_train=N_TRAIN, n_test=2,
              local_epochs=LOCAL_EPOCHS, eval_interval=1e9)
    rows = []
    for n in sizes:
        run_lm_experiment("fedavg", num_clients=n, rounds=1, **kw)  # compile warmup
        t0 = time.perf_counter()
        _, _, _, rep = run_lm_experiment("fedavg", num_clients=n, rounds=rounds, **kw)
        wall = time.perf_counter() - t0
        trained_tokens = rep.up_events * N_TRAIN * SEQ_LEN * LOCAL_EPOCHS
        rows.append({
            "clients": n,
            "uploads_per_s": rep.up_events / wall,
            "tokens_per_s": trained_tokens / wall,
            "delta_kb": delta_bytes / 1024,
            "wall_s": wall,
        })
    return rows


def _model_axis_rows(dim: int, reps: int) -> list[dict]:
    """Pairwise-L1 plane kernel timings at the LM delta width over this
    process's devices: single device, then (with an even count >= 4) an R×M
    mesh with model-axis compute on and off (storage sharded, compute
    replicated)."""
    import jax

    from repro.kernels import ops
    from repro.launch.mesh import make_plane_mesh

    n = len(jax.devices())
    xs = jax.random.normal(jax.random.PRNGKey(0), (512, dim))
    cs = jax.random.normal(jax.random.PRNGKey(1), (8, dim))
    modes = [("single-device", None, "on")]
    if n >= 4 and n % 2 == 0:
        mesh = make_plane_mesh(n // 2, dim_shards=2)
        shape = f"{n // 2}x2"
        modes += [(f"mesh {shape} model-compute on", mesh, "on"),
                  (f"mesh {shape} model-compute off", mesh, "off")]
    rows = []
    knob = os.environ.get("REPRO_PLANE_MODEL_COMPUTE")
    try:
        for name, mesh, compute in modes:
            os.environ["REPRO_PLANE_MODEL_COMPUTE"] = compute
            ops.l1_distance_pairwise(xs, cs, mesh=mesh).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                ops.l1_distance_pairwise(xs, cs, mesh=mesh).block_until_ready()
            rows.append({"mode": name, "l1_us": (time.perf_counter() - t0) / reps * 1e6,
                         "device_kind": jax.devices()[0].device_kind})
    finally:
        if knob is None:
            os.environ.pop("REPRO_PLANE_MODEL_COMPUTE", None)
        else:
            os.environ["REPRO_PLANE_MODEL_COMPUTE"] = knob
    return rows


def bench_model_axis(dim: int, reps: int = 30) -> list[dict]:
    """The model-axis leg over the devices this process already holds. Only
    on the CPU backend with too few devices for a 4x2 mesh does it run in a
    child with a forced 8-device host (a child never needs an accelerator
    that the parent holds). A failing leg raises."""
    import jax

    if jax.default_backend() != "cpu" or len(jax.devices()) >= 4:
        return _model_axis_rows(dim, reps)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join((os.path.join(REPO_ROOT, "src"), REPO_ROOT))
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    code = ("import json; from benchmarks.bench_lm_fleet import _model_axis_rows; "
            f"print(json.dumps(_model_axis_rows({dim}, {reps})))")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(quick: bool = False, sizes: tuple[int, ...] = (8, 16, 32), rounds: int = 2,
        json_out: bool = False) -> dict:
    import jax

    from repro.fl.lm_task import default_lm_task

    if quick:
        sizes, rounds = (4, 8), 1

    task = default_lm_task()
    dim = sum(x.size for x in jax.tree_util.tree_leaves(task.init_params(jax.random.PRNGKey(0))))

    scaling = bench_fleet_scaling(tuple(sizes), rounds)
    model_axis = bench_model_axis(dim, reps=10 if quick else 30)

    print(table(scaling, ["clients", "uploads_per_s", "tokens_per_s", "delta_kb", "wall_s"],
                title=f"LM fleet scaling (tiny_lm, delta dim {dim})"))
    print(table(model_axis, ["mode", "l1_us"],
                title=f"plane pairwise-L1 @ rows of dim {dim}"))

    payload = {
        "base": task.cfg.name,
        "delta_dim": int(dim),
        "seq_len": SEQ_LEN,
        "n_train": N_TRAIN,
        "local_epochs": LOCAL_EPOCHS,
        "rounds": rounds,
        "fleet_scaling": scaling,
        "model_axis_l1": model_axis,
    }
    save_result("lm_fleet", payload)
    if json_out:
        path = os.path.join(REPO_ROOT, "BENCH_lm_fleet.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, default=float)
        print(f"wrote {path}")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="8,16,32")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", action="store_true", help="write BENCH_lm_fleet.json")
    args = ap.parse_args()
    run(quick=args.quick, sizes=tuple(int(s) for s in args.sizes.split(",")),
        rounds=args.rounds, json_out=args.json)


if __name__ == "__main__":
    main()
