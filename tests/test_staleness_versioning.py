"""Staleness accounting (Sec. 5.1) + CI version control (Sec. 6)."""
import threading

import numpy as np
import pytest
from _hypothesis_compat import given, st

from repro.core.staleness import StalenessTracker
from repro.core.versioning import ModelRepo, RWLock


# ------------------------------------------------------------------ staleness
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=50))
def test_tracker_stats_match_numpy(xs):
    t = StalenessTracker()
    for x in xs:
        t.record(x)
    assert t.q_max == max(xs)
    assert np.isclose(t.q_avg, np.mean(xs))
    if max(xs) == 0:
        assert t.convergence_proxy == 0.0
    else:
        assert np.isclose(t.convergence_proxy, np.sqrt(max(xs) * np.mean(xs)))


def test_tracker_rejects_negative():
    t = StalenessTracker()
    with pytest.raises(ValueError):
        t.record(-1)


def test_proxy_is_exactly_zero_without_staleness():
    """Regression: the 1e-12 floors used to leak into staleness-free runs,
    reporting sqrt(1e-12 * 1e-12) instead of 0.0."""
    t = StalenessTracker()
    assert t.convergence_proxy == 0.0  # no records at all
    for _ in range(5):
        t.record(0)
    assert t.convergence_proxy == 0.0  # records, all zero
    t.record(3)
    assert t.convergence_proxy > 0.0  # real staleness still reports


def test_broadcast_lowers_convergence_proxy():
    """The paper's O(sqrt(Qmax*Qavg)) argument: capping staleness (what a
    broadcast does) strictly improves the proxy."""
    with_bcast, without = StalenessTracker(), StalenessTracker()
    stale = [0, 1, 2, 40, 1, 0, 35, 2]
    for s in stale:
        without.record(s)
        with_bcast.record(min(s, 3))  # broadcast refreshes bases
    assert with_bcast.convergence_proxy < without.convergence_proxy


# ----------------------------------------------------------------- versioning
def test_branch_push_pull_roundtrip():
    repo = ModelRepo()
    b = repo.branch("cluster/0", {"w": 0.0})
    assert b.pull() == ({"w": 0.0}, 0)
    v = b.push(lambda head: {"w": head["w"] + 1.0})
    assert v == 1
    assert b.pull() == ({"w": 1.0}, 1)
    assert b.pull(have_version=1) is None   # already current
    assert b.pull(have_version=0) == ({"w": 1.0}, 1)


def test_branch_requires_model_on_create():
    repo = ModelRepo()
    with pytest.raises(KeyError):
        repo.branch("missing")


def test_concurrent_pushes_lose_nothing():
    """The RW-locked push is the paper's conflict-resolution: N threads each
    apply +1; the result must be exactly N (no lost updates)."""
    repo = ModelRepo()
    b = repo.branch("c", {"w": 0})
    n, per = 8, 50

    def worker():
        for _ in range(per):
            b.push(lambda head: {"w": head["w"] + 1})

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    model, version = b.pull()
    assert model["w"] == n * per
    assert version == n * per


def test_concurrent_reads_during_writes():
    b = ModelRepo().branch("c", {"w": 0})
    stop = threading.event = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            model, v = b.pull()
            if model["w"] != v:  # each push keeps w == version
                errors.append((model["w"], v))

    rt = threading.Thread(target=reader)
    rt.start()
    for _ in range(200):
        b.push(lambda head: {"w": head["w"] + 1})
    stop.set()
    rt.join()
    assert not errors, f"torn reads: {errors[:3]}"


def test_merge_branches():
    repo = ModelRepo()
    repo.branch("a", {"w": 1.0})
    repo.branch("b", {"w": 3.0})
    merged = repo.merge_branches("a", "b", lambda dst, src: {"w": (dst["w"] + src["w"]) / 2})
    assert merged.pull()[0] == {"w": 2.0}
    assert repo.names() == ["a"]


def test_rwlock_writer_preference_no_starvation():
    lock = RWLock()
    order = []

    def writer():
        lock.acquire_write()
        order.append("w")
        lock.release_write()

    lock.acquire_read()
    t = threading.Thread(target=writer)
    t.start()
    import time

    time.sleep(0.05)
    assert order == []  # writer blocked by reader
    lock.release_read()
    t.join(timeout=2)
    assert order == ["w"]
