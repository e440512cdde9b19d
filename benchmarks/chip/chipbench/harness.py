"""One benchmark run of a cell: build the fleet and the EchoPFL server from
the seed, warm up, time the coalesced async loop, check what it produced.

The timed path is the program's own entry, ``Simulator.run_async`` with a
coalescing window, the ``plane`` server backend and the ``fleet`` client
backend. The harness adds nothing to the program: it wraps three public
methods from outside, for the length of the run, to take its spans and its
samples, and stops the loop from inside the ingest wrapper:

* ``ClientFleet.set_models`` (downlink installs), ``ClientFleet.train_rows``
  (one fused training launch per superstep) and
  ``EchoPFLServer.handle_uploads`` (batched ingest) are the spans;
* a superstep starts at the first device round-time draw after the
  previous ingest returned (the simulator draws the next round of every
  arrival while it collects a window) and ends when its ingest returns;
* warm-up runs the loop until an ingested batch reaches the traffic's
  virtual horizon; the window opens with the next superstep and closes at
  the first superstep that ends ``seconds`` after it opened.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import sys
import time
from typing import Any

import numpy as np

from . import generators


class WindowClosed(Exception):
    """Raised from the ingest wrapper to end the run at a superstep's end."""


class CompileClock:
    """Counts and sums JAX's tracing, lowering and compile events."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.secs += duration
            self.events += 1


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's own generators (its PRNG keys take
    no more), drawn from ``seed``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


def trail_digest(trail) -> str:
    """A short digest of a sequence of (batch size, first arrival time)."""
    return hashlib.sha256(repr(trail).encode()).hexdigest()[:16]


def flat(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.asarray(x, np.float32).ravel() for x in jax.tree_util.tree_leaves(tree)])


@dataclasses.dataclass
class Samples:
    train: list = dataclasses.field(default_factory=list)  # (cid, base, out, head, lr, epochs)
    ingest: list = dataclasses.field(default_factory=list)  # (pre, post, [(cid, params)])
    ingest_skipped: int = 0


class Recorder:
    """Spans, counts and samples of one run, fed by the method wrappers."""

    INGEST_SAMPLE_EVERY = 2  # every other window superstep snapshots the centers
    REHEARSAL_FACTOR = 1.25
    PROGRESS_S = 10.0

    def __init__(self, *, config: dict, model, horizon: float, seconds: float, refine_every: int, phase: int,
                 clock: CompileClock, trace_dir: str | None, rehearsal: bool = False):
        self.config = config
        self.model = model
        self.rehearsal = rehearsal
        self.horizon = horizon
        self.seconds = seconds
        self.refine_every = refine_every
        self.phase = phase
        self.clock = clock
        self.trace_dir = trace_dir
        self.state = "warmup"  # -> armed -> open -> closed
        self.await_start = True
        self.step_start = 0.0
        self.t_open = self.t_last = self.deadline = None
        self.compile_at_open = (0.0, 0)
        self.ingested_total = 0
        self.trained_total = 0
        self.window_uploads = 0
        self.window_trained = 0
        self.window_flops = 0  # required training FLOPs of the window's uploads
        self.window_steps = 0
        self.replies: list[float] = []
        self.sampling_s = 0.0  # the check's snapshots inside the window, taken out of its time
        self.trail: list = []  # (batch size, first arrival time) of every ingest
        self.spans: dict[str, list] = {"set_models": [], "train": [], "ingest": []}
        self.chain_shapes: list = []  # (steps, centers, dim) of window ingest-chain launches
        self.samples = Samples()
        self.fleet = None
        self.server = None
        self._depth = 0
        self._annotation = None
        self._last_progress = time.perf_counter()

    # ------------------------------------------------------------ window
    def on_draw(self) -> None:
        if not self.await_start:
            return
        self.await_start = False
        if self.state == "armed":
            self._open()
        self.step_start = time.perf_counter()

    def _open(self) -> None:
        if self.trace_dir is not None and not self.rehearsal:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._annotation = jax.profiler.TraceAnnotation("chipbench/window")
            self._annotation.__enter__()
        self.state = "open"
        self.compile_at_open = (self.clock.secs, self.clock.events)
        self.t_open = time.perf_counter()
        self.deadline = self.t_open + self.seconds

    def _window_over(self, now: float) -> bool:
        """The timed pass ends at the first superstep past ``seconds``; the
        rehearsal runs on until it has done ``REHEARSAL_FACTOR`` times that
        much work, compilation taken out, so that it meets every shape the
        timed window will."""
        if not self.rehearsal:
            return now >= self.deadline
        work = now - self.t_open - (self.clock.secs - self.compile_at_open[0])
        return work >= self.REHEARSAL_FACTOR * self.seconds

    def close_trace(self) -> None:
        if self._annotation is not None:
            import jax

            self._annotation.__exit__(None, None, None)
            self._annotation = None
            jax.profiler.stop_trace()

    def _span(self, name: str, fn):
        if self.state != "open":
            return fn()
        if self.trace_dir is not None:
            import jax

            with jax.profiler.TraceAnnotation("chipbench/" + name):
                t0 = time.perf_counter()
                out = fn()
        else:
            t0 = time.perf_counter()
            out = fn()
        self.spans[name].append((t0, time.perf_counter()))
        return out

    # ------------------------------------------------------------ ingest
    def ingest(self, server, batch, call):
        if self._depth:  # handle_uploads falls back to handle_upload inside
            return call()
        self.server = server
        in_window = self.state == "open"
        n0 = self.ingested_total
        refine = (n0 + len(batch)) // self.refine_every != n0 // self.refine_every
        sample = (in_window and len(batch) > 1
                  and self.window_steps % self.INGEST_SAMPLE_EVERY == self.phase % self.INGEST_SAMPLE_EVERY)
        if sample and refine:  # a refine sweep re-clusters mid-batch: not a sequential ingest
            self.samples.ingest_skipped += 1
            sample = False
        pre_s = 0.0
        if sample:
            t0 = time.perf_counter()
            pre = _snapshot(server, batch)
            pre_s = time.perf_counter() - t0
        self._depth += 1
        try:
            out = self._span("ingest", call)
        finally:
            self._depth -= 1
        t1 = time.perf_counter()
        self.ingested_total += len(batch)
        self.trail.append((len(batch), batch[0][4]))
        if in_window:
            self.window_uploads += len(batch)
            self.window_steps += 1
            self.replies.extend([t1 - self.step_start - pre_s] * len(batch))
            self.t_last = t1
            if sample:
                post = _snapshot(server, batch)
                if post["order"] != pre["order"] or _structural(server.events[pre["events"]:]):
                    self.samples.ingest_skipped += 1
                else:
                    self.samples.ingest.append((pre, post, [(b[0], b[1]) for b in batch]))
                self.sampling_s += pre_s + time.perf_counter() - t1
        elif self.state == "warmup":
            self._progress(batch[0][4])
            if batch[0][4] >= self.horizon:
                self.state = "armed"
        self.await_start = True
        if in_window and self._window_over(t1):
            self.state = "closed"
            raise WindowClosed
        return out

    def _progress(self, t_virtual: float) -> None:
        """A warm-up line on stderr every ``PROGRESS_S`` of wall time."""
        now = time.perf_counter()
        if now - self._last_progress < self.PROGRESS_S:
            return
        self._last_progress = now
        print("warmup", json.dumps({
            "virtual_s": round(t_virtual, 1), "uploads": self.ingested_total,
            "compile_s": round(self.clock.secs, 1), "compile_events": self.clock.events,
        }), file=sys.stderr, flush=True)

    # ------------------------------------------------------------- fleet
    def train_rows(self, fleet, cids, call):
        self.fleet = fleet
        in_window = self.state == "open"
        if in_window:
            cs = [fleet.clients[fleet.index[c]] for c in cids]
            bases = [(c.model, c.partial_finetune, c.lr, c.local_epochs) for c in cs]
        out = self._span("train", call)
        self.trained_total += len(cids)
        if in_window:
            self.window_trained += len(cids)
            for cid, (base, head, lr, ep), trained in zip(cids, bases, out[0]):
                self.samples.train.append((cid, base, trained, head, lr, ep))
                self.window_flops += self.model.train_flops_per_upload(self.config, head_only=bool(head))
        return out

    def train_client(self, fleet, cid, call):
        self.fleet = fleet
        out = self._span("train", call)
        self.trained_total += 1
        if self.state == "open":
            self.window_trained += 1
            head = fleet.clients[fleet.index[cid]].partial_finetune
            self.window_flops += self.model.train_flops_per_upload(self.config, head_only=bool(head))
        return out

    def set_models(self, call):
        return self._span("set_models", call)


def _snapshot(server, batch) -> dict:
    """Host copy of the centers and of the batch clients' assignment and
    pins; ``events`` is the length of the server's event log."""
    import jax

    cl = server.clustering
    order = sorted(cl.clusters)
    vecs = jax.device_get([cl.clusters[c].center_vec for c in order])
    clients = {b[0] for b in batch}
    return {
        "order": order,
        "centers": {c: np.asarray(v, np.float32) for c, v in zip(order, vecs)},
        "assign": {k: cl.assignment.get(k) for k in clients},
        "pinned": {(c, k) for c in order for k in cl.clusters[c].partial_finetune if k in clients},
        "events": len(server.events),
    }


def _structural(events) -> bool:
    """Whether the server re-clustered or rolled back (any event but a
    broadcast)."""
    return any(e["kind"] != "broadcast" for e in events)


class _Patches:
    """Class-level method wrappers installed for one run, then removed."""

    def __init__(self):
        self._saved: list = []

    def wrap(self, cls, name, make):
        orig = getattr(cls, name)
        self._saved.append((cls, name, orig))
        setattr(cls, name, make(orig))

    def restore(self):
        for cls, name, orig in reversed(self._saved):
            setattr(cls, name, orig)
        self._saved = []


def _install(rec: Recorder) -> _Patches:
    from repro.core.server import EchoPFLServer
    from repro.fl.fleet import ClientFleet

    p = _Patches()
    p.wrap(EchoPFLServer, "handle_uploads", lambda f: lambda self, batch: rec.ingest(
        self, batch, lambda: f(self, batch)))
    p.wrap(EchoPFLServer, "handle_upload", lambda f: lambda self, *a: rec.ingest(
        self, [a], lambda: f(self, *a)))
    p.wrap(ClientFleet, "train_rows", lambda f: lambda self, cids, **kw: rec.train_rows(
        self, cids, lambda: f(self, cids, **kw)))
    p.wrap(ClientFleet, "train_client", lambda f: lambda self, cid: rec.train_client(
        self, cid, lambda: f(self, cid)))
    p.wrap(ClientFleet, "set_models", lambda f: lambda self, cids, params: rec.set_models(
        lambda: f(self, cids, params)))
    return p


# ------------------------------------------------------------------- build
def build(cell, seed: int, rec: Recorder):
    """Clients, server and simulator of one cell: the traffic's fixed draw
    of data, devices and initial model, with the data and the model drawn
    and permuted by ``seed`` in the configuration's client model."""
    import jax

    from repro.core.client import SimClient
    from repro.fl.experiment import build_strategy
    from repro.fl.network import NetworkModel
    from repro.fl.simulator import Simulator

    config, traffic = cell.config, cell.traffic
    n = config["num_clients"]
    draw = traffic["trajectory_seed"]
    pseed = program_seed(draw)
    rng = np.random.default_rng(draw)
    data, init, client = cell.model.draw(config, rng, pseed, seed)
    devices = generators.make_device_fleet(n, rng, config["device_mix"], config["base_round_time_s"])
    init = jax.device_put(init)

    def timed(round_time_of):
        def round_time():
            rec.on_draw()
            return round_time_of()
        return round_time

    clients = [
        SimClient(client_id=i, data=data[i], device_class=devices[i]["class"],
                  round_time_fn=timed(devices[i]["round_time"]), **client)
        for i in range(n)
    ]
    server = build_strategy(
        "echopfl", init, clients, seed=pseed, num_clusters=config["num_initial_clusters"],
        hm=config["hm"], mix_rate=config["mix_rate"], plane_backend="plane",
    )
    server.refine_every = rec.refine_every
    sim = Simulator(
        clients, server, network=NetworkModel(), eval_interval=1e18, seed=pseed,
        client_backend="fleet", coalesce_window=traffic["coalesce_window_s"],
    )
    return data, clients, server, sim


@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees of a finished run."""

    config: dict
    traffic: dict
    peaks: dict
    setup_s: float
    window_s: float
    uploads: int
    trained: int
    train_flops: int
    replies: list
    spans: dict
    chain_shapes: list
    trace: Any  # chipbench.trace.TraceSummary in a traced run, else None


def _pass(cell, seed: int, seconds: float, clock: CompileClock, *, trace_dir, rehearsal: bool):
    """Build the cell from ``seed`` and drive the loop until the window
    closes; returns the recorder and what the check needs."""
    import repro.kernels.ops as ops

    n = cell.config["num_clients"]
    phase = int(np.random.default_rng(seed).integers(1 << 30))
    rec = Recorder(config=cell.config, model=cell.model, horizon=cell.traffic["warmup_horizon_s"], seconds=seconds,
                   refine_every=max(20, n // 4), phase=phase, clock=clock, trace_dir=trace_dir,
                   rehearsal=rehearsal)
    patches = _install(rec)
    chain = ops.ingest_chain

    def chain_probe(U, centers, *a, num_centers=None, **kw):
        if rec.state == "open":
            rec.chain_shapes.append((int(np.sum(np.asarray(a[3]))), int(num_centers), int(U.shape[1])))
        return chain(U, centers, *a, num_centers=num_centers, **kw)

    ops.ingest_chain = chain_probe
    try:
        rec.t_build = time.perf_counter()
        data, clients, server, sim = build(cell, seed, rec)
        rec.t_built = time.perf_counter()
        try:
            sim.run_async(max_time=1e18)
        except WindowClosed:
            pass
        finally:
            rec.close_trace()
    finally:
        ops.ingest_chain = chain
        patches.restore()
    if rec.state != "closed":
        raise RuntimeError(f"the loop ended before the window closed (state {rec.state})")
    return rec, data, server, sim


@contextlib.contextmanager
def _as_configured(cell):
    """The program as the cell's configuration states it: no ``REPRO_*``
    switches, matmuls at the configured precision; both put back after."""
    import jax

    saved_env = {k: os.environ.pop(k) for k in [k for k in os.environ if k.startswith("REPRO_")]}
    saved_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", saved_precision)
        os.environ.update(saved_env)


def run(cell, seed: int, seconds: float, *, trace_dir: str | None, t_process: float,
        check=None) -> dict:
    """Run one cell; returns the pieces the entry point prints. ``check``
    (default :func:`chipbench.check.check`) compares the samples.

    The program's shapes follow its data (batch sizes, broadcast fan-outs),
    so the run is made twice from the same seed: a rehearsal that meets, and
    compiles or loads from the persistent cache, every shape the timed
    window will use, then the timed pass, which replays the same trajectory
    with nothing left to compile. Both count as set-up. Every seed replays
    the traffic's fixed draw, permuted by the client model, so the
    shapes are the same from seed to seed and only a checkout's first run
    compiles them."""
    import gc

    import jax

    from . import check as check_mod
    from .trace import load_events, summarize

    with _as_configured(cell):
        clock = CompileClock()
        t_init = time.perf_counter()
        first, *_ = _pass(cell, seed, seconds, clock, trace_dir=None, rehearsal=True)
        rehearsal = {"seconds": time.perf_counter() - t_init, "compile_s": clock.secs,
                     "compile_events": clock.events, "warmup_uploads": first.ingested_total - first.window_uploads,
                     "window_uploads": first.window_uploads, "window_supersteps": first.window_steps}
        trail = first.trail
        del first, _
        gc.collect()
        c_before = clock.secs
        rec, data, server, sim = _pass(cell, seed, seconds, clock, trace_dir=trace_dir, rehearsal=False)
        acc = float(np.mean(rec.fleet.evaluate_fleet([server.model_for(c) for c in rec.fleet.ids])))
    common = min(len(rec.trail), len(trail))
    diverged = [i for i in range(common) if rec.trail[i] != trail[i]]
    rehearsal["replayed"] = not diverged
    rehearsal["timed_ingests_past_rehearsal"] = len(rec.trail) - common
    if diverged:
        k = diverged[0]
        rehearsal["first_divergence"] = [k, rec.trail[k], trail[k]]
    devices = jax.devices()[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    summary = summarize(load_events(trace_dir)) if trace_dir is not None else None
    record = RunRecord(
        config=cell.config, traffic=cell.traffic, peaks=None, setup_s=rec.t_open - t_process,
        window_s=rec.t_last - rec.t_open - rec.sampling_s, uploads=rec.window_uploads, trained=rec.window_trained,
        train_flops=rec.window_flops,
        replies=rec.replies, spans=rec.spans, chain_shapes=rec.chain_shapes, trace=summary,
    )
    t_check = time.perf_counter()
    result = (check or check_mod.check)(cell, seed, rec, data, sim)
    t_check = time.perf_counter() - t_check
    info = {
        "setup_parts_s": {
            "init": t_init - t_process, "rehearsal": rehearsal["seconds"],
            "build": rec.t_built - rec.t_build, "warmup": rec.t_open - rec.t_built,
            "compile_in_rehearsal": rehearsal["compile_s"],
            "compile_in_timed_warmup": rec.compile_at_open[0] - c_before,
        },
        "window": {
            "seconds": record.window_s, "sampling_s": rec.sampling_s, "uploads": rec.window_uploads, "supersteps": rec.window_steps,
            "compile_events": clock.events - rec.compile_at_open[1],
            "compile_s": clock.secs - rec.compile_at_open[0],
        },
        "rehearsal": rehearsal,
        "warmup_uploads": rec.ingested_total - rec.window_uploads,
        "warmup_trail": trail_digest(rec.trail[:len(rec.trail) - rec.window_steps]),
        "server": {k: v for k, v in server.stats().items() if k in ("clusters", "merges", "expansions", "broadcasts", "plane_rows")},
        "plane_capacity": server.clustering.plane.capacity,
        "final_mean_acc": acc,
        "ingest_samples": len(rec.samples.ingest), "ingest_skipped": rec.samples.ingest_skipped,
        "check_s": t_check,
    }
    return {"record": record, "memory_peak_bytes": int(peak), "check": result, "info": info,
            "devices": devices}
