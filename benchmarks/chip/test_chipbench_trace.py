"""The trace reduction: device busy time as the union of operation
intervals, the idle share, program sums and idle gaps attributed to the
host span open at the time — on hand-made events with hand counts."""
from __future__ import annotations

import pytest

from chipbench.trace import OPS_LINE, MODULES_LINE, WINDOW, Event, module_seconds, summarize

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def test_hand_counted_window():
    events = [
        _ev(HOST, "python", WINDOW, 0, 100),
        _ev(HOST, "python", "chipbench/train", 5, 20),
        _ev(HOST, "python", "chipbench/ingest", 40, 50),
        # overlapping ops count once: busy 10..30 and 50..60, clipped at 100
        _ev(DEV, OPS_LINE, "fusion.1", 10, 15),
        _ev(DEV, OPS_LINE, "fusion.2", 20, 10),
        _ev(DEV, OPS_LINE, "ingest_kernel", 50, 10),
        _ev(DEV, OPS_LINE, "late", 95, 10),
        _ev(DEV, OPS_LINE, "before", -5, 3),
        _ev(DEV, MODULES_LINE, "jit__ingest_chain_jit", 50, 10),
        _ev(DEV, MODULES_LINE, "jit__train_launch_bank", 10, 20),
    ]
    s = summarize(events)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((20 + 10 + 5) / 1e3)
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(65.0)
    assert module_seconds(s, "ingest_chain") == pytest.approx(0.01)
    assert module_seconds(s, "predictor_chain") is None
    # gaps by midpoint: 60-95 at 77.5 (ingest 40-90), 30-50 at 40 (ingest opens),
    # 0-10 at 5 (train opens)
    assert s.idle_gaps == [("ingest", pytest.approx(0.035)), ("ingest", pytest.approx(0.02)),
                           ("train", pytest.approx(0.01))]
    quiet = summarize([e for e in events if not e.name.startswith("chipbench/t")])
    assert quiet.idle_gaps[-1] == ("loop", pytest.approx(0.01))


def test_device_time_is_averaged_over_chips():
    events = [
        _ev(HOST, "python", WINDOW, 0, 10),
        _ev(DEV, OPS_LINE, "a", 0, 4),
        _ev("/device:TPU:1", OPS_LINE, "a", 0, 2),
    ]
    s = summarize(events)
    assert s.devices == 2 and s.busy_s == pytest.approx(0.003)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        summarize([_ev(DEV, OPS_LINE, "a", 0, 1)])
