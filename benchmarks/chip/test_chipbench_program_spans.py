"""The reduction of the program's own spans: clipping to the window, self
time, per-upload sums, syncs per superstep and the exact overlap of stage
spans with the device's idle time — on hand-made events with hand counts,
and the loader on a trace the program writes."""
from __future__ import annotations

import types

import pytest

from chipbench import program_spans as ps
from chipbench import spec
from chipbench.trace import OPS_LINE, WINDOW, Event

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
A, B = (HOST, 0), (HOST, 1)  # two thread lines


def _sp(name, t0, t1, line=A, **stats):
    return ps.Span(name, line, t0 * 1e6, t1 * 1e6, stats)


def _ev(plane, name, t0, t1):
    return Event(plane, OPS_LINE if plane.startswith("/device:") else "python", name, t0 * 1e6,
                 (t1 - t0) * 1e6)


def _window(t0=0, t1=100):
    return [_ev(HOST, WINDOW, t0, t1)]


def test_self_time_takes_out_children_on_the_same_line():
    spans = [
        _sp("ingest", 0, 100),
        _sp("ingest/chain", 10, 30),
        _sp("sync", 12, 20),
        _sp("ingest/replay", 40, 50),
        _sp("sync", 20, 60, line=B),  # another thread: no child of ingest
        _sp("ingest/refine", 50, 50),  # zero length, at the replay's end
    ]
    got = [t / 1e6 for t in ps.self_ns(spans)]
    assert got == [100 - 20 - 10, 20 - 8, 8, 10, 40, 0]


def test_clip_to_the_window():
    spans = [_sp("superstep", -5, 10), _sp("collect", -5, -1), _sp("ingest", 95, 120)]
    got = ps.clip(spans, 0.0, 100e6)
    assert [(s.name, s.start_ns / 1e6, s.end_ns / 1e6) for s in got] == [
        ("superstep", 0, 10), ("ingest", 95, 100)]


def _trace():
    events = _window() + [
        _ev(DEV0, "fusion", 10, 20), _ev(DEV0, "fusion", 50, 60), _ev(DEV0, "late", 98, 130),
    ]
    spans = [
        _sp("superstep", -10, 100, superstep=1),
        _sp("collect", 0, 5),
        _sp("install", 5, 15, rows=3),
        _sp("install/flatten", 6, 9),
        _sp("ingest", 15, 98, uploads=4),
        _sp("sync", 15, 40, site="chain"),
        _sp("ingest/predictor", 40, 55),
        _sp("sync", 50, 55, site="predictor"),
        _sp("ingest/replay", 70, 90),
        _sp("ingest/refine", 75, 85, moved=1),
        _sp("sync", 80, 84, site="chi2"),
        _sp("install", 200, 210),  # after the window: dropped
    ]
    return ps.reduce(events, spans)


def test_per_upload_sums_and_syncs_per_superstep(monkeypatch):
    p = _trace()
    run = types.SimpleNamespace(uploads=4, trace=object(), spans={"ingest": [(0, 1), (1, 2)]})
    monkeypatch.setattr(ps, "of", lambda r: p)

    def read(name):
        return spec.metric_reader(name)(run)

    assert read("install_ms_per_upload") == pytest.approx(10 / 4)
    assert read("predictor_ms_per_upload") == pytest.approx(15 / 4)
    assert read("ingest_replay_ms_per_upload") == pytest.approx((20 - 10) / 4)
    assert read("refine_ms_per_sweep") == pytest.approx(10)
    assert read("sync_ms_per_upload") == pytest.approx((25 + 5 + 4) / 4)
    assert read("syncs_per_superstep") == pytest.approx(3 / 2)
    # idle 0-10, 20-50, 60-98 (98-100 busy): 78 ms. Stages cover 0-5, 6-9
    # (flatten, inside the install that is no stage), 15-55 and 70-90:
    # 0-5, 6-9, 20-50 and 70-90 of the idle time, 58 ms of 78.
    assert read("idle_explained_share") == pytest.approx(100 * 58 / 78)


def test_idle_overlap_is_averaged_over_devices():
    spans = [_sp("collect", 0, 5), _sp("sync", 15, 40), _sp("ingest", 0, 100),
             _sp("ingest/replay", 70, 90)]
    busy = {DEV0: [(10e6, 20e6), (50e6, 60e6)], DEV1: []}
    # device 0: idle 80 ms, covered 5 + 20 + 20; device 1: idle 100, covered 50
    got = ps.idle_explained(busy, spans, 0.0, 100e6)
    assert got == pytest.approx((45 / 80 + 50 / 100) / 2)
    assert ps.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_nothing_to_read_gives_none(monkeypatch):
    untraced = types.SimpleNamespace(uploads=4, trace=None, spans={"ingest": [(0, 1)]})
    # a program that writes no spans, as the parent of this reader wrote
    assert ps.reduce(_window() + [_ev(DEV0, "fusion", 10, 20)], []) is None
    monkeypatch.setattr(ps, "of", lambda r: None)
    for m in ("install_ms_per_upload", "ingest_replay_ms_per_upload", "predictor_ms_per_upload",
              "refine_ms_per_sweep", "sync_ms_per_upload", "syncs_per_superstep",
              "idle_explained_share"):
        assert spec.metric_reader(m)(untraced) is None, m
    monkeypatch.undo()
    assert ps.of(untraced) is None


def test_loader_reads_what_the_program_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.common import tracing

    x = jnp.arange(4.0) + 1
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("superstep", superstep=7) as step:
            tracing.fetch(x, "chain")
            step.set_metadata(uploads=3)
    assert ps.newest(tmp_path / "none") is None
    spans = {s.name: s for s in ps.load_spans(ps.newest(tmp_path))}
    assert set(spans) == {"superstep", "sync"}
    assert spans["superstep"].stats == {"superstep": 7, "uploads": 3}
    assert spans["sync"].stats == {"site": "chain"}
    assert spans["sync"].line == spans["superstep"].line
    assert spans["superstep"].start_ns <= spans["sync"].start_ns <= spans["sync"].end_ns \
        <= spans["superstep"].end_ns
