"""Poison-resilient ingest: the update guard and quarantine ledger.

PR 9 made the transport layer hostile-but-survivable; the value path was
still fully trusting — nothing checked an incoming delta before
``assign_and_lerp`` blended it into a shared cluster center, so one NaN,
Inf, or magnitude-blown upload (bitflips, broken quantization,
adversarial clients — Papaya's production failure modes in PAPERS.md)
corrupted the center, and EchoPFL's own on-demand broadcast then
amplified the blast radius to every cluster member, the predictor's
change/gap statistics, and the chi2 feedback loop.

:class:`IngestGuard` closes the value path. Per delivered upload it
scores three host-side statistics and accepts or rejects *before* the
strategy sees the payload:

* **finite mask** — any NaN/Inf coordinate is an unconditional reject;
* **L2 norm** of the uploaded vector — catches magnitude blowups
  (``REPRO_FAULT_POISON_SCALE``) against a robust per-cluster bound;
* **L1 distance to the client's current cluster center** — catches
  direction attacks (``REPRO_FAULT_POISON_SIGN``: a sign-flipped model
  has the *same* norm but lands far from every center). Checked (and
  recorded) only when the client's cluster home is unchanged since its
  last accepted upload: right after a reassignment or merge a client is
  legitimately far from a center whose history it never fed, so the
  distance gate waits one settled round instead of false-positive
  striking honest movers.

Thresholds are robust running statistics per cluster: the median and
MAD (median absolute deviation) over the last ``window`` *accepted*
values, with the bound ``med + k * max(1.4826 * mad, rel_floor * med)``.
Rejected values never enter the history, so a poisoning client cannot
drag the threshold toward its own uploads. A ``grace`` cold-start
window accepts unconditionally-finite uploads until each cluster has
enough history for the median to mean anything (non-finite uploads are
rejected even during grace — NaN needs no statistics).

Escalation: every rejection is a strike. At ``quarantine_strikes`` the
client enters persistent quarantine (uploads keep billing bytes — the
transport already spent them — but are auto-rejected and ledgered); at
``evict_strikes`` the simulator retires the client entirely through the
same eviction path device death uses, reclaiming its plane rows.

Late detection — center rollback
--------------------------------
A poison can slip a finite, modest-norm corruption past the per-upload
gate (or the guard can be attached with poison already blended in). As
a second line the server checks the *post-blend center norm*, computed
inside the existing fused ``ingest_chain`` launch (``with_stats`` adds
one scalar per step to the already-synced stats vector — no extra
launches or host syncs), against the same MAD discipline via
:meth:`IngestGuard.center_ok`. A failed check rolls the cluster center
back to the last-known-good snapshot ring entry
(:meth:`~repro.core.clustering.Cluster.rollback`) and re-broadcasts on
demand — recovery is just another EchoPFL broadcast with staleness
accounting, not a new protocol.

Determinism contract
--------------------
``REPRO_GUARD=off`` (the default) constructs nothing: the simulator
holds ``guard=None``, every hook is behind an ``is None`` check, and
trajectories are bitwise-identical to the pre-guard code. ``on`` over a
clean run is all-accept by construction (stats ride existing launches
and syncs; thresholds live on host and are generous multiples of the
robust spread), so clean guard-on trajectories are *also*
bitwise-identical — the guard only ever changes a run that a poison
would otherwise have corrupted. tests/test_guard.py pins both.

Knobs: ``REPRO_GUARD`` (``off``/``on``); thresholds are code defaults
on :class:`GuardConfig` (constructor-overridable, not env-mapped — the
env switch is the contract surface, the statistics are implementation).
"""
from __future__ import annotations

import dataclasses
import math
import os
from collections import deque
from typing import Any

import numpy as np

__all__ = [
    "GuardConfig",
    "IngestGuard",
    "guard_enabled",
    "resolve_guard",
]


def guard_enabled() -> bool:
    """``REPRO_GUARD`` ambient switch (``1``/``on`` enables)."""
    return os.environ.get("REPRO_GUARD", "").strip().lower() in ("1", "on", "true", "yes")


@dataclasses.dataclass
class GuardConfig:
    """Robust-threshold + escalation parameters (see module docstring)."""

    grace: int = 8  # accepted finite uploads per cluster before bounds engage
    window: int = 64  # history length per cluster for median/MAD
    k: float = 12.0  # bound = med + k * max(1.4826*mad, rel_floor*med)
    rel_floor: float = 1.0  # spread floor relative to the median
    quarantine_strikes: int = 3
    evict_strikes: int = 6
    snapshot_ring: int = 2  # last-known-good center snapshots per cluster

    def __post_init__(self):
        for name in ("grace", "window", "quarantine_strikes", "evict_strikes",
                     "snapshot_ring"):
            v = getattr(self, name)
            if v < 0:
                raise ValueError(f"{name} must be >= 0, got {v!r}")
        if self.evict_strikes < self.quarantine_strikes:
            raise ValueError(
                "evict_strikes must be >= quarantine_strikes, got "
                f"{self.evict_strikes} < {self.quarantine_strikes}")
        for name in ("k", "rel_floor"):
            if getattr(self, name) <= 0.0:
                raise ValueError(f"{name} must be > 0, got {getattr(self, name)!r}")


def resolve_guard(spec: Any = None) -> GuardConfig | None:
    """Normalize the simulator's ``guard=`` argument.

    ``None`` consults ``REPRO_GUARD`` (ambient default); ``"off"``
    forces the guard away regardless of the environment; ``"on"`` or a
    :class:`GuardConfig` enables it. Returns ``None`` when disabled —
    the simulator then constructs nothing and every guard hook is inert."""
    if spec is None:
        return GuardConfig() if guard_enabled() else None
    if isinstance(spec, str):
        low = spec.strip().lower()
        if low in ("", "0", "off", "none", "no"):
            return None
        if low in ("1", "on", "true", "yes"):
            return GuardConfig()
        raise ValueError(f"guard spec must be on|off or a GuardConfig; got {spec!r}")
    if isinstance(spec, GuardConfig):
        return spec
    raise ValueError(f"guard spec must be on|off or a GuardConfig; got {spec!r}")


def _leaves(tree: Any) -> list[np.ndarray]:
    """Host-numpy leaves of a pytree without importing jax here: the
    payloads the guard sees are mostly host numpy views already; a
    device payload (a one-client training launch) pays one read."""
    import jax

    from repro.common.tracing import fetch

    leaves = jax.tree_util.tree_leaves(tree)
    if any(isinstance(x, jax.Array) for x in leaves):
        leaves = fetch(leaves, "guard")
    return [np.asarray(x) for x in leaves]


def _robust_bound(hist: deque, k: float, rel_floor: float) -> float:
    vals = np.asarray(hist, dtype=np.float64)
    med = float(np.median(vals))
    mad = float(np.median(np.abs(vals - med)))
    spread = max(1.4826 * mad, rel_floor * abs(med), 1e-12)
    return med + k * spread


class IngestGuard:
    """Per-upload accept/reject + strike escalation + rollback bookkeeping.

    One guard lives per :class:`~repro.fl.simulator.Simulator` run; the
    simulator consults it at the single upload funnel both async loops
    share, and the server consults :meth:`center_ok` after each blend.
    All state is host-side Python/numpy — nothing here touches a device."""

    def __init__(self, cfg: GuardConfig | None = None):
        self.cfg = cfg or GuardConfig()
        self._norm_hist: dict[Any, deque] = {}
        self._dist_hist: dict[Any, deque] = {}
        self._center_hist: dict[Any, deque] = {}
        self._last_home: dict[Any, Any] = {}  # cid -> cluster at last accept
        self._strikes: dict[Any, int] = {}
        self.quarantined: set = set()
        self.evicted: set = set()
        self.ledger: dict[str, Any] = {
            "accepted": 0,
            "rejected_nonfinite": 0,
            "rejected_norm": 0,
            "rejected_dist": 0,
            "rejected_quarantined": 0,
            "rollbacks": 0,
            "quarantined_clients": 0,
            "evicted_clients": 0,
        }

    # ------------------------------------------------------------- stats
    def upload_stats(self, update: Any, center: Any | None) -> tuple[bool, float, float]:
        """``(finite, l2_norm, l1_dist_to_center)`` of an upload, in host
        numpy (float64 accumulation so the stats themselves can't
        overflow on a poisoned payload). ``center=None`` (no cluster
        yet) reports ``dist = 0`` — the norm and finite gates still apply."""
        sq = 0.0
        dist = 0.0
        finite = True
        c_leaves = _leaves(center) if center is not None else None
        for i, u in enumerate(_leaves(update)):
            u64 = u.astype(np.float64, copy=False)
            if finite and not bool(np.all(np.isfinite(u64))):
                finite = False
            sq += float(np.sum(u64 * u64))
            if c_leaves is not None:
                dist += float(np.sum(np.abs(u64 - c_leaves[i].astype(np.float64, copy=False))))
        l2 = math.sqrt(sq) if math.isfinite(sq) else float("inf")
        if not finite:
            l2 = float("inf")
            dist = float("inf")
        return finite, l2, dist

    # ---------------------------------------------------------- decision
    def check_upload(self, cid: Any, cluster_key: Any, finite: bool,
                     l2: float, dist: float) -> str:
        """Gate one delivered upload. Returns ``accept`` or a reject
        reason (``nonfinite``/``norm``/``dist``/``quarantined``).
        Accepted stats enter the per-cluster history; every reject is a
        strike that escalates to quarantine then (via
        :meth:`should_evict`) eviction."""
        if cid in self.quarantined:
            self.ledger["rejected_quarantined"] += 1
            self._strike(cid)
            return "quarantined"
        if not finite:
            return self._reject(cid, "nonfinite")
        nh = self._norm_hist.setdefault(cluster_key, deque(maxlen=self.cfg.window))
        dh = self._dist_hist.setdefault(cluster_key, deque(maxlen=self.cfg.window))
        if nh and len(nh) >= self.cfg.grace and l2 > _robust_bound(nh, self.cfg.k, self.cfg.rel_floor):
            return self._reject(cid, "norm")
        # the distance statistic only means something for a *settled*
        # member: right after a reassignment or merge the client is
        # legitimately far from a center whose history it never fed, so
        # the check (and the history append) waits one accepted round
        stable = self._last_home.get(cid) == cluster_key
        if (stable and dh and len(dh) >= self.cfg.grace
                and dist > _robust_bound(dh, self.cfg.k, self.cfg.rel_floor)):
            return self._reject(cid, "dist")
        nh.append(l2)
        if stable:
            dh.append(dist)
        self._last_home[cid] = cluster_key
        self.ledger["accepted"] += 1
        return "accept"

    def _reject(self, cid: Any, reason: str) -> str:
        self.ledger[f"rejected_{reason}"] += 1
        self._strike(cid)
        return reason

    def _strike(self, cid: Any) -> None:
        n = self._strikes.get(cid, 0) + 1
        self._strikes[cid] = n
        if n >= self.cfg.quarantine_strikes and cid not in self.quarantined:
            self.quarantined.add(cid)
            self.ledger["quarantined_clients"] += 1

    def should_evict(self, cid: Any) -> bool:
        """True exactly once, when the strike count crosses the eviction
        bar — the simulator then retires the client through the same
        path permanent device death uses."""
        if cid in self.evicted:
            return False
        if self._strikes.get(cid, 0) >= self.cfg.evict_strikes:
            self.evicted.add(cid)
            self.ledger["evicted_clients"] += 1
            return True
        return False

    # ----------------------------------------------------- late detection
    def center_ok(self, cluster_key: Any, cnorm: float) -> bool:
        """Post-blend check on a cluster center's L1 norm (computed
        inside the fused ingest launch and synced with the stats the
        server already pulls). NaN/Inf or a MAD-bound blowout vetoes the
        blend — the caller rolls the center back. Healthy norms enter
        the per-cluster history."""
        v = float(cnorm)
        if not math.isfinite(v):
            return False
        hist = self._center_hist.setdefault(cluster_key, deque(maxlen=self.cfg.window))
        if hist and len(hist) >= self.cfg.grace and v > _robust_bound(hist, self.cfg.k, self.cfg.rel_floor):
            return False
        hist.append(v)
        return True

    def note_rollback(self) -> None:
        self.ledger["rollbacks"] += 1

    # ------------------------------------------------------------ ledger
    def ledger_snapshot(self) -> dict:
        out = dict(self.ledger)
        out["quarantined"] = sorted(map(repr, self.quarantined))
        out["evicted"] = sorted(map(repr, self.evicted))
        out["strikes"] = sum(self._strikes.values())
        return out
