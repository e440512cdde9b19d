"""Deterministic fault injection for the async protocol.

The simulator's virtual world has, until now, been a friendly one: every
device that starts a local round finishes it, every upload crosses the
thin link on the first try, every downlink arrives exactly once and in
order, and the server never dies mid-run. Production federated systems
(Papaya is the reference point in PAPERS.md) live in the opposite
regime — device churn and transport failures dominate — so this module
injects exactly those faults, *deterministically*, so chaos runs are as
reproducible and parity-testable as clean ones:

- **client crash mid-local-round**: the round's work is lost and the
  device goes dark for a drawn downtime, rejoining through the same
  ``_next_online`` path static churn uses; a configurable fraction of
  crashes are permanent (device death), after which the server reclaims
  the client's protocol state (see ``EchoPFLServer.evict_clients``).
- **upload loss/timeout with capped exponential-backoff retries**: each
  failed attempt bills its full payload bytes and transfer duration plus
  a backoff through :class:`~repro.fl.network.NetworkModel` (flagged so
  retry-attributable bytes are reported separately), and the added delay
  flows into version-based staleness accounting for free. Under the
  ``drop`` policy the sender gives up after ``max_retries`` failures
  instead — the drop-the-straggler baseline the bench compares against.
- **duplicate delivery**: the upload arrives twice (the retransmission
  bills real bytes); the ingest path absorbs the second copy through a
  per-client monotonic sequence fence.
- **downlink reorder**: a broadcast leg is delayed past a later send;
  the client install path fences on a per-recipient send sequence so a
  stale model never overwrites a newer one.
- **server kill + restore mid-``run_async``**: the live strategy is
  checkpointed through :mod:`repro.checkpoint`, discarded, and a fresh
  instance restored from disk — continuing the run must reproduce the
  uninterrupted ledger exactly.

Determinism contract
--------------------
Every decision is drawn from a :class:`numpy.random.SeedSequence` keyed
by ``(seed, fault kind, client id hash, per-(kind, client) counter)`` —
*never* from a shared stream. The two async paths (per-event and
coalesced) and the two client backends (loop and fleet) consult the
injector at different wall points and in different batch shapes; keying
each draw by its own counter makes the schedule a pure function of "the
n-th time this client hit this fault point", which is identical across
all four combinations. A fixed ``REPRO_FAULT_SEED`` therefore yields the
identical fault schedule everywhere, and the chaos parity tests extend
the existing bitwise suites. With faults disabled the simulator never
constructs an injector, so clean trajectories stay bitwise-identical to
the pre-fault code.

Knobs (all read by :func:`default_fault_config`):

``REPRO_FAULTS``              master switch (``1``/``on`` enables)
``REPRO_FAULT_SEED``          schedule seed (default 0)
``REPRO_FAULT_CRASH``         P(crash) per local round (default 0.05)
``REPRO_FAULT_CRASH_DOWNTIME``mean crash downtime seconds (default 120)
``REPRO_FAULT_DEATH``         P(crash is permanent) (default 0.0)
``REPRO_FAULT_LOSS``          P(loss/timeout) per upload attempt (0.1)
``REPRO_FAULT_MAX_RETRIES``   retry cap per upload (default 4)
``REPRO_FAULT_BACKOFF``       base backoff seconds, doubled per retry (5)
``REPRO_FAULT_BACKOFF_CAP``   backoff ceiling seconds (default 60)
``REPRO_FAULT_DUP``           P(duplicate delivery) per upload (0.05)
``REPRO_FAULT_REORDER``       P(extra delay) per downlink (0.05)
``REPRO_FAULT_POLICY``        ``retry`` (default) or ``drop``
``REPRO_FAULT_POISON_NAN``    P(delivered upload turns partly NaN) (0.0)
``REPRO_FAULT_POISON_SCALE``  P(delivered upload magnitude-blown) (0.0)
``REPRO_FAULT_POISON_SIGN``   P(delivered upload sign-flipped) (0.0)
``REPRO_FAULT_POISON_FACTOR`` scale blowup factor (default 1e3)

Value-level poison (the ``POISON`` knobs) corrupts the *post-codec*
upload delta after transport succeeds — the model for bitflips, broken
quantizers, and adversarial clients rather than lost packets. One draw
per delivered upload partitions a single uniform across the three
corruption kinds, so the schedule stays a pure per-``(kind, cid,
counter)`` function and the per-event/coalesced loops and loop/fleet
backends poison the identical uploads. The defense layer that catches
these lives in :mod:`repro.fl.guard` (``REPRO_GUARD=on``).
"""
from __future__ import annotations

import dataclasses
import os
import zlib
from typing import Any, Callable

import numpy as np

# fault-kind codes for the draw key: stable small ints, never reordered
_K_CRASH = 1
_K_UPLOAD = 2
_K_DUP = 3
_K_REORDER = 4
_K_POISON = 5


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    return float(raw) if raw else default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else default


def faults_enabled() -> bool:
    """``REPRO_FAULTS`` master switch."""
    return os.environ.get("REPRO_FAULTS", "").strip().lower() in ("1", "on", "true", "yes")


@dataclasses.dataclass
class FaultConfig:
    """Per-kind fault rates + retry discipline (see module docstring)."""

    seed: int = 0
    crash_rate: float = 0.05
    crash_downtime: float = 120.0  # mean; draw is uniform in [0.5, 1.5) x mean
    death_rate: float = 0.0  # fraction of crashes that are permanent
    loss_rate: float = 0.1  # per upload attempt
    max_retries: int = 4
    backoff_base: float = 5.0
    backoff_cap: float = 60.0
    dup_rate: float = 0.05
    reorder_rate: float = 0.05
    reorder_max_delay: float = 60.0
    dup_max_delay: float = 30.0
    policy: str = "retry"  # retry | drop (drop-the-straggler baseline)
    poison_nan_rate: float = 0.0  # per delivered upload
    poison_scale_rate: float = 0.0
    poison_sign_rate: float = 0.0
    poison_scale_factor: float = 1e3
    poison_nan_frac: float = 0.01  # fraction of coordinates NaN'd

    def __post_init__(self):
        if self.policy not in ("retry", "drop"):
            raise ValueError(f"REPRO_FAULT_POLICY must be retry|drop, got {self.policy!r}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        for name in ("crash_rate", "death_rate", "loss_rate", "dup_rate",
                     "reorder_rate", "poison_nan_rate", "poison_scale_rate",
                     "poison_sign_rate", "poison_nan_frac"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name} must be a probability in [0, 1], got {v!r}")
        total = self.poison_nan_rate + self.poison_scale_rate + self.poison_sign_rate
        if total > 1.0:
            raise ValueError(
                f"poison rates must sum to <= 1 (one corruption per upload), got {total!r}")
        for name in ("crash_downtime", "backoff_base", "backoff_cap",
                     "reorder_max_delay", "dup_max_delay"):
            v = getattr(self, name)
            if v < 0.0:
                raise ValueError(f"{name} must be >= 0 seconds, got {v!r}")
        if self.poison_scale_factor <= 0.0:
            raise ValueError(
                f"poison_scale_factor must be > 0, got {self.poison_scale_factor!r}")


def default_fault_config() -> FaultConfig:
    """Build a :class:`FaultConfig` from the ``REPRO_FAULT*`` environment."""
    return FaultConfig(
        seed=_env_int("REPRO_FAULT_SEED", 0),
        crash_rate=_env_float("REPRO_FAULT_CRASH", 0.05),
        crash_downtime=_env_float("REPRO_FAULT_CRASH_DOWNTIME", 120.0),
        death_rate=_env_float("REPRO_FAULT_DEATH", 0.0),
        loss_rate=_env_float("REPRO_FAULT_LOSS", 0.1),
        max_retries=_env_int("REPRO_FAULT_MAX_RETRIES", 4),
        backoff_base=_env_float("REPRO_FAULT_BACKOFF", 5.0),
        backoff_cap=_env_float("REPRO_FAULT_BACKOFF_CAP", 60.0),
        dup_rate=_env_float("REPRO_FAULT_DUP", 0.05),
        reorder_rate=_env_float("REPRO_FAULT_REORDER", 0.05),
        policy=os.environ.get("REPRO_FAULT_POLICY", "retry").strip().lower() or "retry",
        poison_nan_rate=_env_float("REPRO_FAULT_POISON_NAN", 0.0),
        poison_scale_rate=_env_float("REPRO_FAULT_POISON_SCALE", 0.0),
        poison_sign_rate=_env_float("REPRO_FAULT_POISON_SIGN", 0.0),
        poison_scale_factor=_env_float("REPRO_FAULT_POISON_FACTOR", 1e3),
    )


def apply_poison(params: Any, kind: str, u: float, cfg: FaultConfig) -> Any:
    """Corrupt one delivered upload per the drawn poison ``(kind, u)``.

    Always builds fresh host arrays — payload leaves may be frozen views
    shared with the client's own model or a codec bank, and the fault
    must corrupt only what crossed the wire. ``nan`` overwrites a
    deterministic ``poison_nan_frac`` slice of each leaf starting at an
    offset derived from ``u`` (the draw's second uniform), so the exact
    corrupted coordinates are part of the seeded schedule; ``scale``
    multiplies by ``poison_scale_factor``; ``sign`` negates."""
    import jax

    from repro.common.tracing import fetch

    leaves, treedef = jax.tree_util.tree_flatten(params)
    if any(isinstance(x, jax.Array) for x in leaves):
        leaves = fetch(leaves, "poison")
    out = []
    for x in leaves:
        a = np.array(x)
        if kind == "sign":
            a = -a
        elif kind == "scale":
            a = a * a.dtype.type(cfg.poison_scale_factor)
        else:  # nan
            flat = a.reshape(-1)
            n = flat.size
            if n:
                cnt = max(1, int(round(cfg.poison_nan_frac * n)))
                idx = (int(u * n) + np.arange(cnt)) % n
                flat[idx] = np.nan
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass
class ServerRestartPlan:
    """Kill + restore the server mid-``run_async``: once ``at_uploads``
    uploads have been ingested, the live strategy's :meth:`state_dict` is
    written through the checkpointer, the object discarded, and
    ``strategy_factory()``'s fresh instance restored from disk. The run
    then continues on the restored server — the acceptance bar is that
    the final report matches an uninterrupted run's ledger exactly."""

    at_uploads: int
    directory: str
    strategy_factory: Callable[[], Any]
    client_id_type: type = int


@dataclasses.dataclass
class FaultPlan:
    """Everything the simulator needs to run a chaos leg: the seeded
    per-kind rates plus an optional mid-run server restart."""

    config: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    restart: ServerRestartPlan | None = None


def resolve_faults(spec: Any = None) -> FaultPlan | None:
    """Normalize the simulator's ``faults=`` argument.

    ``None`` consults ``REPRO_FAULTS`` (the ambient default); ``"off"``
    forces clean runs regardless of the environment; a
    :class:`FaultConfig` / :class:`FaultPlan` is adopted as-is. Returns
    ``None`` when faults are fully disabled — the simulator then never
    touches any fault path, keeping clean trajectories bitwise-identical."""
    if spec is None:
        return FaultPlan(config=default_fault_config()) if faults_enabled() else None
    if isinstance(spec, str):
        low = spec.strip().lower()
        if low in ("", "0", "off", "none", "no"):
            return None
        if low in ("1", "on", "true", "yes"):
            return FaultPlan(config=default_fault_config())
        raise ValueError(f"faults spec must be on|off, a FaultConfig or a FaultPlan; got {spec!r}")
    if isinstance(spec, FaultConfig):
        return FaultPlan(config=spec)
    if isinstance(spec, FaultPlan):
        return spec
    raise ValueError(f"faults spec must be on|off, a FaultConfig or a FaultPlan; got {spec!r}")


class FaultInjector:
    """Order-independent seeded fault schedule + the run's fault ledger.

    One injector lives per :class:`~repro.fl.simulator.Simulator` run.
    Each query advances a per-``(kind, client)`` counter and derives its
    uniforms from ``SeedSequence((seed, kind, crc32(client), counter))``,
    so the schedule depends only on how many times each fault point was
    hit per client — not on the global interleaving, which differs
    between the per-event and coalesced loops."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.cfg = plan.config
        self._counters: dict[tuple[int, int], int] = {}
        self._restart_done = False
        self.ledger: dict[str, Any] = {
            "crashes": 0,
            "deaths": 0,
            "crash_downtime_s": 0.0,
            "upload_failures": 0,
            "retried_uploads": 0,
            "retry_delay_s": 0.0,
            "dropped_uploads": 0,
            "dropped_clients": 0,
            "dups_injected": 0,
            "dups_absorbed": 0,
            "reorders_injected": 0,
            "stale_downlinks_absorbed": 0,
            "server_restarts": 0,
            "evicted_clients": 0,
            "reclaimed_clusters": 0,
            "poison_nan": 0,
            "poison_scale": 0,
            "poison_sign": 0,
        }

    # ------------------------------------------------------------- draws
    def _draw(self, kind: int, cid: Any, n: int) -> np.ndarray:
        key = (kind, zlib.crc32(repr(cid).encode()))
        count = self._counters.get(key, 0)
        self._counters[key] = count + 1
        ss = np.random.SeedSequence(entropy=(self.cfg.seed, kind, key[1], count))
        return np.random.default_rng(ss).random(n)

    def crash(self, cid: Any) -> float | None:
        """Consulted once per local-round start. ``None``: no crash.
        ``inf``: permanent death. Otherwise the downtime in seconds."""
        cfg = self.cfg
        if cfg.crash_rate <= 0.0:
            return None
        u = self._draw(_K_CRASH, cid, 3)
        if u[0] >= cfg.crash_rate:
            return None
        self.ledger["crashes"] += 1
        if cfg.death_rate > 0.0 and u[1] < cfg.death_rate:
            self.ledger["deaths"] += 1
            return float("inf")
        downtime = float(cfg.crash_downtime * (0.5 + u[2]))
        self.ledger["crash_downtime_s"] += downtime
        return downtime

    def upload_plan(self, cid: Any) -> tuple[int, bool]:
        """One decision per upload: ``(failed_attempts, delivered)``.

        Geometric in the per-attempt loss rate, capped at
        ``max_retries`` failures. Under the ``retry`` policy the attempt
        after the last failure always delivers (the capped-backoff
        sender keeps the device in the protocol); under ``drop``,
        hitting the cap abandons the upload — and the client."""
        cfg = self.cfg
        if cfg.loss_rate <= 0.0:
            return 0, True
        u = self._draw(_K_UPLOAD, cid, max(cfg.max_retries, 1))
        fails = 0
        while fails < cfg.max_retries and u[fails] < cfg.loss_rate:
            fails += 1
        self.ledger["upload_failures"] += fails
        if fails:
            self.ledger["retried_uploads"] += 1
        if cfg.policy == "drop" and fails >= cfg.max_retries:
            self.ledger["dropped_uploads"] += 1
            return fails, False
        return fails, True

    def backoff(self, attempt: int) -> float:
        """Backoff after the ``attempt``-th failure (0-indexed),
        exponential with a ceiling."""
        return min(self.cfg.backoff_base * (2.0**attempt), self.cfg.backoff_cap)

    def duplicate(self, cid: Any) -> float | None:
        """Consulted once per delivered upload: ``None`` or the extra
        delay after the original arrival at which the duplicate lands."""
        cfg = self.cfg
        if cfg.dup_rate <= 0.0:
            return None
        u = self._draw(_K_DUP, cid, 2)
        if u[0] >= cfg.dup_rate:
            return None
        self.ledger["dups_injected"] += 1
        return float(1.0 + u[1] * (cfg.dup_max_delay - 1.0))

    def reorder(self, cid: Any) -> float:
        """Consulted once per downlink send to ``cid``: extra delivery
        delay (0.0 = in order)."""
        cfg = self.cfg
        if cfg.reorder_rate <= 0.0:
            return 0.0
        u = self._draw(_K_REORDER, cid, 2)
        if u[0] >= cfg.reorder_rate:
            return 0.0
        self.ledger["reorders_injected"] += 1
        return float(1.0 + u[1] * (cfg.reorder_max_delay - 1.0))

    def poison(self, cid: Any) -> tuple[str, float] | None:
        """Consulted once per *delivered* upload (after transport wins,
        before ingest). ``None``: the delta is clean. Otherwise
        ``(kind, u)`` with ``kind`` in ``nan|scale|sign`` and ``u`` a
        second uniform the corruptor may use (NaN coordinate offset).
        One uniform is partitioned across the three rates so at most one
        corruption applies per upload and adding a kind never perturbs
        another kind's schedule."""
        cfg = self.cfg
        total = cfg.poison_nan_rate + cfg.poison_scale_rate + cfg.poison_sign_rate
        if total <= 0.0:
            return None
        u = self._draw(_K_POISON, cid, 2)
        if u[0] < cfg.poison_nan_rate:
            kind = "nan"
        elif u[0] < cfg.poison_nan_rate + cfg.poison_scale_rate:
            kind = "scale"
        elif u[0] < total:
            kind = "sign"
        else:
            return None
        self.ledger[f"poison_{kind}"] += 1
        return kind, float(u[1])

    # ----------------------------------------------------------- restart
    def restart_due(self, uploads: int) -> bool:
        plan = self.plan.restart
        return plan is not None and not self._restart_done and uploads >= plan.at_uploads

    def mark_restarted(self) -> None:
        self._restart_done = True
        self.ledger["server_restarts"] += 1

    # ------------------------------------------------------------ ledger
    def ledger_snapshot(self) -> dict:
        out = dict(self.ledger)
        out["policy"] = self.cfg.policy
        out["seed"] = self.cfg.seed
        return out
