"""The program's spans (``repro.common.tracing``): a coalesced run under the
JAX profiler writes the span tree into the profiler's own trace, every
device-to-host read of the loop goes through ``fetch``, and tracing does
not change the trajectory."""
from __future__ import annotations

import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.common import tracing
from repro.fl.experiment import build_clients, build_strategy
from repro.fl.network import NetworkModel
from repro.fl.simulator import Simulator

PARENTS = {  # span -> the spans it may open inside (None: top level)
    "superstep": {None},
    "collect": {"superstep"},
    "install": {"superstep", None},
    "install/flatten": {"install"},
    "train": {"superstep"},
    "train/launch": {"train"},
    "ingest": {"superstep"},
    "ingest/chain": {"ingest"},
    "ingest/predictor": {"ingest"},
    "ingest/replay": {"ingest"},
    "ingest/single": {"ingest"},
    "ingest/refine": {"ingest/replay", "ingest/single"},
    "bill": {"superstep"},
}
ANYWHERE = {"sync", "plane/stage", "plane/flush"}
READS = ("__array__", "__float__", "__int__", "__bool__", "__index__", "item", "tolist")
CONVERTERS = ("asarray", "asanyarray", "array")  # on the CPU these read through the buffer protocol


def _run(loop=lambda run: run()):
    """A 64-client coalesced run; ``loop`` wraps the call of the loop."""
    _, clients, init = build_clients("har", 64, seed=3, samples_per_client=48)
    strat = build_strategy("echopfl", init, clients, seed=3)
    sim = Simulator(clients, strat, network=NetworkModel(), seed=3, eval_interval=1e18,
                    client_backend="fleet", coalesce_window=5.0)
    report = loop(lambda: sim.run_async(max_time=300.0))
    cl = strat.clustering
    centers = {c: np.asarray(cl.clusters[c].center_vec).tobytes() for c in sorted(cl.clusters)}
    trail = (sim.coalesced_groups, strat.events, dict(cl.assignment), report.up_bytes,
             report.down_bytes, report.duration, strat.staleness)
    return trail, centers


def _spans(trace_dir: str) -> list[tuple]:
    """(line key, name, start, end, stats) of every program span."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append(((plane.name, li), e.name[len(tracing.PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One untraced run that records every device read the loop makes
    outside ``jax.device_get`` and every ``device_get`` not made by
    ``fetch``; then the same seed under the profiler."""
    implicit: list = []
    foreign: list = []
    saved = {n: getattr(ArrayImpl, n) for n in READS}
    converters = {n: getattr(np, n) for n in CONVERTERS}
    get = jax.device_get
    inside = [0]

    def note(name):
        if not inside[0]:
            implicit.append((name, sys._getframe(2).f_code.co_filename, sys._getframe(2).f_lineno))

    def watch(name):
        def read(self, *a, **k):
            note(name)
            return saved[name](self, *a, **k)
        return read

    def convert(name):
        def read(x, *a, **k):
            if isinstance(x, jax.Array):
                note(name)
            return converters[name](x, *a, **k)
        return read

    def device_get(x):
        if sys._getframe(1).f_code is not tracing.fetch.__code__:
            foreign.append(sys._getframe(1).f_code.co_filename)
        inside[0] += 1
        try:
            return get(x)
        finally:
            inside[0] -= 1

    def watched(run):
        try:
            for n in READS:
                setattr(ArrayImpl, n, watch(n))
            for n in CONVERTERS:
                setattr(np, n, convert(n))
            jax.device_get = device_get
            return run()
        finally:
            jax.device_get = get
            for n, f in saved.items():
                setattr(ArrayImpl, n, f)
            for n, f in converters.items():
                setattr(np, n, f)

    def traced_loop(run):
        with jax.profiler.trace(trace_dir):
            return run()

    plain = _run(watched)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    traced = _run(traced_loop)
    return {"plain": plain, "traced": traced, "spans": _spans(trace_dir),
            "implicit": implicit, "foreign": foreign}


def test_span_is_the_shared_no_op_without_a_session(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled() and not tracing.on()
    off = tracing.span("superstep", superstep=1)
    assert off is tracing.span("sync", site="x") is tracing.OFF
    with off as s:
        s.set_metadata(uploads=3)
    with jax.profiler.trace(str(tmp_path)):
        assert tracing.on() and tracing.span("collect") is not tracing.OFF
    assert not tracing.on()


def test_fetch_returns_host_arrays():
    a, b = tracing.fetch((jnp.arange(3), jnp.ones(2)), "test")
    assert isinstance(a, np.ndarray) and a.tolist() == [0, 1, 2] and b.tolist() == [1.0, 1.0]


def test_every_read_of_the_loop_goes_through_fetch(runs):
    assert runs["implicit"] == []
    assert runs["foreign"] == []


def test_tracing_leaves_the_trajectory_bitwise(runs):
    assert runs["plain"] == runs["traced"]


def test_span_tree(runs):
    spans = runs["spans"]
    names = {s[1] for s in spans}
    assert set(PARENTS) - {"ingest/single"} <= names  # a 64-client run meets every stage
    by_line: dict = {}
    for s in spans:
        by_line.setdefault(s[0], []).append(s)
    for line in by_line.values():
        stack: list = []
        for key, name, t0, t1, stats in sorted(line, key=lambda s: (s[2], -s[3])):
            while stack and stack[-1][3] <= t0:
                stack.pop()
            parent = stack[-1] if stack else None
            if parent is not None:
                assert t1 <= parent[3], f"{name} overlaps {parent[1]} without nesting"
            if name not in ANYWHERE:
                assert (parent[1] if parent else None) in PARENTS[name], (name, parent and parent[1])
            stack.append((key, name, t0, t1, stats))
    syncs = [s for s in spans if s[1] == "sync"]
    assert syncs and all(s[4].get("site") for s in syncs)
    assert {"fleet_train", "chain", "predictor", "feedback", "chi2"} <= {s[4]["site"] for s in syncs}
    steps = sorted((s for s in spans if s[1] == "superstep"), key=lambda s: s[2])
    assert [s[4]["superstep"] for s in steps] == list(range(1, len(steps) + 1))
    assert all({"downlinks", "starts", "uploads"} <= set(s[4]) for s in steps)
    ingests = [s for s in spans if s[1] == "ingest"]
    assert sum(s[4]["uploads"] for s in ingests) == sum(s[4]["uploads"] for s in steps)
    chains = [s[4] for s in spans if s[1] == "ingest/chain"]
    assert all(c["steps"] >= 2 and c["padded"] >= 0 and c["centers"] >= 1 for c in chains)
    refines = [s[4] for s in spans if s[1] == "ingest/refine"]
    assert refines and all({"moved", "expansions", "merges", "dissolves"} <= set(r) for r in refines)


def test_install_span_counts_distinct_payloads(runs):
    installs = [s[4] for s in runs["spans"] if s[1] == "install"]
    assert installs and all(1 <= s["distinct"] <= s["rows"] for s in installs)
    assert any(s["distinct"] < s["rows"] for s in installs)  # a broadcast fans one center out
