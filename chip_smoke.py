#!/usr/bin/env python3
"""Smoke run of EchoPFL's main path on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: phases "kernels", "har", "lm"
    python chip_smoke.py --chips 4   # four chips: "har" sharded vs single-device

Phases (one chip):

* ``kernels`` — every public ``repro.kernels.ops`` entry at har width (a
  4,550-float plane row) or tiny_lm width, compared on the chip with the
  oracles of ``repro.kernels.ref`` at f32 tolerance; the lowering of each
  must hold a Pallas kernel (``tpu_custom_call``).
* ``har`` — ``build_clients``/``build_strategy``/``Simulator`` on the paper's
  har MLP: 4,096 clients, the EchoPFL server on the plane backend, the fleet
  client backend and the coalesced async loop (45 s windows), until 3,072
  uploads are ingested over at least three windows. Accuracy must beat the 1/6 chance level and the
  uplink byte ledger must be exact.
* ``lm`` — ``run_experiment`` under ``REPRO_TASK=lm`` (LoRA/head deltas on a
  frozen tiny_lm): fedavg and echopfl rounds, so the flash attention forward
  and backward kernels run compiled under the fleet's client vmap.

With ``--chips 4`` the har phase runs twice in one process, once with the
plane and the fleet sharded four ways (``REPRO_PLANE_MESH``,
``REPRO_FLEET_MESH``) and once on one device, and the two server
trajectories must be identical: assignments, events and stats equal,
centers allclose.

Lines before the last are smoke output, not benchmark numbers. The last
line is one JSON object naming the device. The script exits non-zero, and
prints no such line, when the backend is not a TPU, when ``REPRO_KERNELS``
is set to anything but ``auto`` or ``REPRO_ATTN_COST_PROXY`` is set, or when
any phase fails. It starts no other process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
HAR_CLIENTS = 4096
HAR_UPLOADS = 3072  # the first three 45 s windows at 4,096 clients
HAR_WINDOW_S = 45.0
HAR_ROW_FLOATS = 4550
SEED = 0
KERNEL_MARK = "tpu_custom_call"  # how a compiled Pallas kernel shows in a lowering


def _fail(msg: str, code: int = 2):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


def _say(phase: str, **info) -> None:
    print(f"smoke {phase}: {json.dumps(info, default=float)}", flush=True)


class CompileClock:
    """Sums JAX's tracing, lowering and backend-compile durations."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += duration


# --------------------------------------------------------------- kernels
def _close(name, got, want, rtol, atol):
    import jax
    import numpy as np

    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            raise AssertionError(f"{name}: shape {g.shape} != reference {w.shape}")
        if np.issubdtype(g.dtype, np.integer) or g.dtype == bool:
            if not np.array_equal(g, w):
                raise AssertionError(f"{name}: {g} != reference {w}")
        else:
            np.testing.assert_allclose(g, w, rtol=rtol, atol=atol, err_msg=name)


def _ingest_chain_ref(U, centers, bcast, prev, forced, valid, beta, margin):
    """Sequential per-upload ingest on the L1 oracle: what ``ingest_chain``
    computes in one launch."""
    import numpy as np

    from repro.kernels import ref

    cm = np.array(centers, np.float32)
    outs = []
    for u, p, f, ok in zip(np.asarray(U), prev, forced, valid):
        d = np.asarray(ref.l1_distance_ref(u, cm))
        amin = int(np.argmin(d))
        veto = p >= 0 and p != amin and d[amin] > (1.0 - margin) * d[p]
        cid = f if f >= 0 else (p if veto else amin)
        old = cm[cid].copy()
        new = np.float32(1.0 - beta) * old + np.float32(beta) * u
        b = np.asarray(bcast)[cid]
        outs.append((cid, new, np.abs(new - old).sum(), np.abs(old - b).sum(),
                     np.abs(new - b).sum()))
        if ok:
            cm[cid] = new
    return tuple(np.stack(col) for col in zip(*outs))


def phase_kernels() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    f32 = np.float32
    N, C, M, S, J = HAR_ROW_FLOATS, 8, 64, 16, 6
    u = jnp.asarray(rng.standard_normal(N), f32)
    cen = jnp.asarray(rng.standard_normal((C, N)), f32)
    xs = jnp.asarray(rng.standard_normal((M, N)), f32)
    U = jnp.asarray(rng.standard_normal((S, N)), f32)
    bcast = jnp.asarray(rng.standard_normal((C, N)), f32)
    prev = np.where(np.arange(S) % 3 == 0, -1, np.arange(S) % C).astype(np.int32)
    forced = np.where(np.arange(S) == 5, 2, -1).astype(np.int32)
    valid = np.arange(S) != 7
    fp = jnp.asarray(rng.uniform(0, 100, (M, J)), f32)
    ft = jnp.asarray(rng.uniform(1, 100, (M, J)), f32)
    ss = jax.nn.softmax(jnp.asarray(rng.standard_normal((M, J)), f32), axis=-1)
    seg = jnp.asarray(np.arange(M) % 4, np.int32)
    onehot = (seg[:, None] == jnp.arange(4)[None, :]).astype(f32)

    # tiny_lm attention shapes under the fleet's client vmap: (clients, B, H, S, hd)
    cfg = get_config("tiny_lm")
    hd = cfg.resolved_head_dim
    B, Sq, nc = 8, 32, 4
    q = jnp.asarray(rng.standard_normal((nc, B, cfg.num_heads, Sq, hd)), f32)
    kv = jnp.asarray(rng.standard_normal((nc, B, cfg.num_kv_heads, Sq, hd)), f32)
    w = jnp.asarray(rng.standard_normal(q.shape), f32)

    def attn(q, k, v):
        return jax.vmap(lambda q, k, v: ops.attention(q, k, v, causal=True))(q, k, v)

    def attn_ref(q, k, v):
        return jax.vmap(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))(q, k, v)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * w)

    beta = 0.25
    # (name, entry, args, oracle[, tolerance]): 2e-5 as tests/test_kernels.py;
    # the gradient 3e-4 as tests/test_attention_grads.py (the backward
    # recomputes its tiles in another order than autodiff of the oracle)
    cases = [
        ("ingest_chain",
         lambda U, c, b: ops.ingest_chain(U, c, b, prev, forced, valid, beta=beta),
         (U, cen, bcast),
         lambda: _ingest_chain_ref(U, cen, bcast, prev, forced, valid, beta, 0.1)),
        ("assign_and_lerp", lambda u, c: ops.assign_and_lerp(u, c, beta), (u, cen),
         lambda: ref.assign_and_lerp_ref(u, cen, beta)),
        ("l1_distance_pairwise", ops.l1_distance_pairwise, (xs, cen),
         lambda: ref.l1_distance_pairwise_ref(xs, cen)),
        ("chi2_feedback", ops.chi2_feedback, (fp, ft, ss),
         lambda: ref.chi2_feedback_ref(fp, ft, ss)),
        ("chi2_feedback_all", lambda a, b, c, s: ops.chi2_feedback_all(a, b, c, s, 4),
         (fp, ft, ss, seg), lambda: ref.chi2_feedback_segmented_ref(fp, ft, ss, onehot)),
        ("merge_attention", ops.merge_attention, (u, cen[0], cen[1]),
         lambda: ref.merge_attention_ref(u, cen[0], cen[1])[0]),
        ("attention", attn, (q, kv, kv), lambda: attn_ref(q, kv, kv)),
        ("attention_grad", jax.grad(loss(attn), argnums=(0, 1, 2)), (q, kv, kv),
         lambda: jax.grad(loss(attn_ref), argnums=(0, 1, 2))(q, kv, kv), 3e-4),
    ]
    for name, fn, args, want, *tol in cases:
        if KERNEL_MARK not in jax.jit(fn).lower(*args).as_text():
            raise AssertionError(f"{name}: no Pallas kernel in its lowering")
        with jax.default_matmul_precision("float32"):
            expect = want()
        tol = tol[0] if tol else 2e-5
        _close(name, fn(*args), expect, rtol=tol, atol=tol)
    return {"entries": [c[0] for c in cases], "width": N}


# ------------------------------------------------------------------- har
def run_har(clients: int, uploads: int, *, sharded: bool = False):
    """One coalesced async EchoPFL run on har; returns (server, report, sim)."""
    import jax

    from repro.fl.experiment import build_clients, build_strategy
    from repro.fl.network import NetworkModel
    from repro.fl.simulator import Simulator

    spec = str(len(jax.devices())) if sharded else "off"
    os.environ["REPRO_PLANE_MESH"] = spec
    os.environ["REPRO_FLEET_MESH"] = spec
    _, fleet, init = build_clients("har", clients, seed=SEED)
    server = build_strategy("echopfl", init, fleet, seed=SEED, plane_backend="plane")
    server.refine_every = max(20, clients // 4)  # as bench_async_coalesce
    sim = Simulator(fleet, server, network=NetworkModel(), seed=SEED,
                    client_backend="fleet", coalesce_window=HAR_WINDOW_S)
    report = sim.run_async(max_time=1e9, max_uploads=uploads)
    return server, report, sim


def _har_summary(server, report, sim, clients, uploads) -> dict:
    n_up = report.extra["uploads"]
    windows = len(sim.coalesced_groups.get("upload_done", []))
    chance = 1.0 / 6.0
    row_bytes = HAR_ROW_FLOATS * 4
    if n_up < uploads or windows < 3:
        raise AssertionError(f"har: {n_up} uploads over {windows} windows")
    if not report.final_acc > chance:
        raise AssertionError(f"har: accuracy {report.final_acc} not above chance {chance}")
    if report.up_bytes != report.up_events * row_bytes or report.up_raw_bytes != report.up_bytes:
        raise AssertionError(
            f"har: uplink ledger {report.up_bytes} B for {report.up_events} uploads "
            f"of {row_bytes} B (raw {report.up_raw_bytes} B)")
    stats = server.stats()
    return {
        "clients": clients, "uploads_ingested": n_up, "windows": windows,
        "final_mean_acc": report.final_acc, "chance": chance,
        "uplink_bytes": report.up_bytes, "uplink_events": report.up_events,
        "uplink_bytes_per_upload": row_bytes, "downlink_bytes": report.down_bytes,
        "clusters": stats["clusters"], "broadcasts": stats["broadcasts"],
        "plane_rows": stats["plane_rows"],
    }


def phase_har() -> dict:
    server, report, sim = run_har(HAR_CLIENTS, HAR_UPLOADS)
    return _har_summary(server, report, sim, HAR_CLIENTS, HAR_UPLOADS)


_TRAJECTORY_STATS = ("clusters", "merges", "expansions", "staleness", "broadcasts",
                     "rnn_broadcasts", "decisions", "plane_rows")


def phase_har_sharded() -> dict:
    """The sharded plane and fleet against the single-device run, same seed."""
    import jax
    import numpy as np

    sharded, rep_s, sim_s = run_har(HAR_CLIENTS, HAR_UPLOADS, sharded=True)
    for what, mesh in (("plane", sharded.clustering.plane.mesh), ("fleet", sim_s._fleet.mesh)):
        if mesh is None or mesh.devices.size != len(jax.devices()):
            raise AssertionError(f"har sharded: {what} mesh {mesh}")
    per_chip = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    info = _har_summary(sharded, rep_s, sim_s, HAR_CLIENTS, HAR_UPLOADS)
    single, rep_1, sim_1 = run_har(HAR_CLIENTS, HAR_UPLOADS)
    if single.clustering.plane.mesh is not None or sim_1._fleet.mesh is not None:
        raise AssertionError("har single-device: a mesh is active")
    if sharded.clustering.assignment != single.clustering.assignment:
        raise AssertionError("har sharded: assignments differ from single-device")
    if sharded.events != single.events:
        raise AssertionError("har sharded: server events differ from single-device")
    ss, s1 = sharded.stats(), single.stats()
    for key in _TRAJECTORY_STATS:
        if ss[key] != s1[key]:
            raise AssertionError(f"har sharded: stats[{key}] {ss[key]} != {s1[key]}")
    worst = 0.0
    for cid, c in single.clustering.clusters.items():
        a = sharded.clustering.clusters[cid]
        for x, y in zip(jax.tree_util.tree_leaves(a.center), jax.tree_util.tree_leaves(c.center)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-6)
            worst = max(worst, float(np.max(np.abs(np.asarray(x) - np.asarray(y)))))
    info.update(shards=len(jax.devices()), single_device_final_mean_acc=rep_1.final_acc,
                trajectory="identical", max_center_abs_diff=worst,
                bytes_in_use_per_chip_after_sharded_run=per_chip)
    return info


# -------------------------------------------------------------------- lm
def phase_lm() -> dict:
    from repro.fl.experiment import run_experiment

    os.environ["REPRO_TASK"] = "lm"
    try:
        out = {}
        for strategy in ("fedavg", "echopfl"):
            _, _, _, rep = run_experiment("har", strategy, num_clients=16, seed=SEED,
                                          rounds=3, max_time=600.0, client_backend="fleet")
            if rep.up_events < 1:
                raise AssertionError(f"lm {strategy}: no uploads")
            out[strategy] = {"uplink_events": rep.up_events, "uplink_bytes": rep.up_bytes,
                             "final_mean_acc": rep.final_acc}
        return out
    finally:
        del os.environ["REPRO_TASK"]


# ------------------------------------------------------------------ main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    if (os.environ.get("REPRO_KERNELS") or "auto") != "auto":
        _fail(f"REPRO_KERNELS={os.environ['REPRO_KERNELS']}: the smoke runs the auto dispatch")
    if "REPRO_ATTN_COST_PROXY" in os.environ:
        _fail("REPRO_ATTN_COST_PROXY is set: it swaps the reference attention in")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from repro.common.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX's backend is {dev.platform}")
    if len(jax.devices()) < args.chips:
        _fail(f"--chips {args.chips} but JAX sees {len(jax.devices())} devices")
    cache = use_compile_cache()
    _say("device", platform=dev.platform, kind=dev.device_kind, count=len(jax.devices()),
         compile_cache=cache)

    phases = ([("har_sharded", phase_har_sharded)] if args.chips == 4 else
              [("kernels", phase_kernels), ("har", phase_har), ("lm", phase_lm)])
    clock = CompileClock()
    for name, fn in phases:
        c0, t0 = clock.secs, time.perf_counter()
        try:
            info = fn()
        except Exception:
            traceback.print_exc()
            _fail(f"phase {name} failed", code=1)
        _say(name, device_kind=dev.device_kind, compile_s=clock.secs - c0,
             wall_s=time.perf_counter() - t0, **info)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
