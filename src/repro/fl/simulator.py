"""Event-driven federated-learning simulator.

Replays the paper's experimental setup in virtual time: heterogeneous
devices (D1..D5 latency model), asymmetric up/down bandwidth, and a
pluggable coordination strategy (EchoPFL or any baseline). Asynchronous
strategies run on an event heap; synchronous ones run round barriers
(optionally per-cluster barriers, for ClusterFL).

The simulator measures exactly what the paper reports: accuracy-vs-time
curves, per-client accuracy (slowest/fastest device), total/up/down
communication bytes, per-minute communication series (peaks), staleness
statistics, and time-to-target-accuracy.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import math
import os
from typing import Any

import jax
import numpy as np

from repro.common import tracing
from repro.common.tracing import span
from repro.core.client import SimClient
from repro.fl.faults import FaultInjector, resolve_faults
from repro.fl.network import NetworkModel

PyTree = Any


def default_client_backend() -> str:
    """``REPRO_CLIENT`` knob: ``fleet`` (batched launches via
    :mod:`repro.fl.fleet` — the default since the CI soak) or ``loop``
    (per-client dispatches, the seed path — kept as the parity leg)."""
    return os.environ.get("REPRO_CLIENT", "fleet").lower()


def default_async_coalesce() -> float:
    """``REPRO_ASYNC_COALESCE`` knob: virtual-time window (seconds) for
    coalescing concurrent async events into batched launches. ``off`` /
    ``0`` / unset keeps the per-event loop (the parity default)."""
    spec = os.environ.get("REPRO_ASYNC_COALESCE", "off").strip().lower()
    if spec in ("", "0", "off", "none", "no"):
        return 0.0
    return float(spec)


@dataclasses.dataclass
class SimReport:
    strategy: str
    curve: list[tuple[float, float]]  # (t, mean acc)
    per_client_acc: dict[int, float]
    per_client_class: dict[int, str]
    final_acc: float
    time_to_target: float | None
    up_bytes: int
    down_bytes: int
    up_events: int
    down_events: int
    peak_down: float
    peak_up: float
    duration: float
    extra: dict
    up_series: dict = dataclasses.field(default_factory=dict)  # minute -> bytes
    down_series: dict = dataclasses.field(default_factory=dict)
    # dense-equivalent uplink bytes: equals up_bytes unless an uplink codec
    # (REPRO_UPLINK) compressed the wire — the ratio is the comm-cost claim
    up_raw_bytes: int = 0
    # retry-attributable uplink bytes: re-sends after losses/timeouts and
    # duplicate retransmissions under fault injection (REPRO_FAULTS)
    up_retry_bytes: int = 0

    def bytes_until(self, t: float) -> tuple[float, float]:
        """(up, down) bytes accumulated in bins up to time t (the paper's
        communication-to-convergence metric)."""
        last = int(t // 60)
        up = sum(v for b, v in self.up_series.items() if b <= last)
        down = sum(v for b, v in self.down_series.items() if b <= last)
        return up, down

    def summary(self) -> dict:
        out = {
            "strategy": self.strategy,
            "final_acc": round(self.final_acc, 4),
            "time_to_target_min": None if self.time_to_target is None else round(self.time_to_target / 60, 2),
            "duration_min": round(self.duration / 60, 2),
            "up_MB": round(self.up_bytes / 1e6, 2),
            "down_MB": round(self.down_bytes / 1e6, 2),
            "total_MB": round((self.up_bytes + self.down_bytes) / 1e6, 2),
            "peak_down_MB_per_min": round(self.peak_down / 1e6, 2),
            "peak_up_MB_per_min": round(self.peak_up / 1e6, 2),
        }
        if self.up_raw_bytes and self.up_raw_bytes != self.up_bytes:
            out["up_raw_MB"] = round(self.up_raw_bytes / 1e6, 2)
            out["uplink_ratio"] = round(self.up_bytes / self.up_raw_bytes, 4)
        if self.up_retry_bytes:
            out["up_retry_MB"] = round(self.up_retry_bytes / 1e6, 2)
        return out


_MODEL_BYTES_CACHE: dict = {}


def model_bytes(params: PyTree) -> int:
    """Wire size of one model payload: sum of per-leaf nbytes. Leaf dtype is
    honored — a compressed/quantized payload (int8, fp16) is not 4 bytes per
    element; non-array leaves (python scalars) count as 4-byte words.

    Memoized by (treedef, leaf shapes/dtypes): both simulator loops bill
    every uplink/downlink event through this function with the same handful
    of model structures, so repeat events pay one tree walk and a hash
    lookup instead of the per-leaf arithmetic. Deliberately NOT keyed by
    object identity — that would pin payload pytrees (and their device
    buffers) in a module-global for the process lifetime."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    key = (
        treedef,
        tuple((getattr(x, "shape", None), getattr(x, "dtype", None)) for x in leaves),
    )
    total = _MODEL_BYTES_CACHE.get(key)
    if total is None:
        total = 0
        for x in leaves:
            dtype = getattr(x, "dtype", None)
            itemsize = dtype.itemsize if dtype is not None else 4
            total += int(np.prod(getattr(x, "shape", ()))) * itemsize
        if len(_MODEL_BYTES_CACHE) > 64:
            _MODEL_BYTES_CACHE.clear()
        _MODEL_BYTES_CACHE[key] = total
    return total


class Simulator:
    def __init__(
        self,
        clients: list[SimClient],
        strategy,
        *,
        network: NetworkModel | None = None,
        eval_interval: float = 60.0,
        target_acc: float = 0.85,
        seed: int = 0,
        churn: dict[Any, list[tuple[float, float]]] | None = None,
        client_backend: str | None = None,
        coalesce_window: float | None = None,
        uplink: Any | None = None,
        faults: Any | None = None,
        guard: Any | None = None,
    ):
        from repro.fl.guard import IngestGuard, resolve_guard
        from repro.fl.uplink import resolve_uplink

        self.clients = {c.client_id: c for c in clients}
        self.strategy = strategy
        self.net = network or NetworkModel()
        # uplink compression (REPRO_UPLINK): config resolves now, the codec
        # itself builds lazily with the fleet (it needs the model template)
        self.uplink = resolve_uplink(uplink)
        self._codec = None
        self.eval_interval = eval_interval
        self.target_acc = target_acc
        self.rng = np.random.default_rng(seed)
        self.curve: list[tuple[float, float]] = []
        self._counter = itertools.count()
        self.client_backend = (client_backend or default_client_backend()).lower()
        if self.client_backend not in ("loop", "fleet"):
            raise ValueError(
                f"REPRO_CLIENT backend must be loop|fleet, got {self.client_backend}"
            )
        self.coalesce_window = (
            float(coalesce_window) if coalesce_window is not None else default_async_coalesce()
        )
        self.coalesced_groups: dict[str, list[int]] = {}  # kind -> group sizes (bench introspection)
        self._fleet = None  # built lazily from the first initial model
        # elastic membership: {client: [(t_offline, t_back), ...]} — a device
        # that would start local training inside an offline window instead
        # resumes when it returns (dropout/rejoin; the async protocol absorbs
        # both, which is what the fault-tolerance tests assert)
        self.churn = churn or {}
        self.churn_delays = 0
        # fault injection (REPRO_FAULTS / the faults= argument): None when
        # disabled — every fault branch below is then dead, keeping clean
        # trajectories bitwise-identical to the pre-fault code
        plan = resolve_faults(faults)
        self._faults = FaultInjector(plan) if plan is not None else None
        # ingest guard (REPRO_GUARD / the guard= argument): None when off —
        # every guard hook below is then dead, keeping guard-off trajectories
        # bitwise-identical to the pre-guard code
        gcfg = resolve_guard(guard)
        self._guard = IngestGuard(gcfg) if gcfg is not None else None
        self._dead: set = set()  # permanently-dark clients (death / drop policy)
        self._useq: dict[Any, int] = {}  # per-client upload send sequence
        self._ingest_high: dict[Any, int] = {}  # highest useq ingested (dup fence)
        self._dl_seq: dict[Any, int] = {}  # per-recipient downlink send sequence
        self._dl_high: dict[Any, int] = {}  # highest fseq installed (reorder fence)
        self._template = None  # model template, kept for post-restart rewiring

    def _next_online(self, cid, t: float) -> float:
        """Single churn consultation point for a local-round start: static
        churn windows first, then injected crashes (the crash loses the
        round's work and the device resumes through this same path —
        ``inf`` marks a permanent death)."""
        for t_off, t_on in self.churn.get(cid, ()):
            if t_off <= t < t_on:
                self.churn_delays += 1
                return t_on
        if self._faults is not None:
            down = self._faults.crash(cid)
            if down is not None:
                if down == math.inf:
                    return math.inf
                self.churn_delays += 1
                return t + down
        return t

    # -------------------------------------------------------- fleet engine
    def _ensure_fleet(self, template: PyTree) -> None:
        """Build the batched client engine (REPRO_CLIENT=fleet) once the
        model structure is known, and hand the strategy its batched
        feedback probe if it accepts one. A hook installed by a *previous*
        simulator's fleet (strategy objects can be reused across runs) is
        always replaced — or cleared on the loop backend — so probes never
        route through a dead fleet's clients/data."""
        strat = self.strategy
        if self.uplink.mode != "none":
            if self._codec is None:
                from repro.fl.uplink import UplinkCodec

                # both backends compress: the codec is its own batched launch,
                # so even the per-client loop ships compressed (B = 1) uploads
                self._codec = UplinkCodec(template, list(self.clients), self.uplink)
            attach = getattr(strat, "attach_uplink_codec", None)
            if attach is not None and getattr(strat, "uplink_codec", None) is not self._codec:
                # the strategy adopts the codec so anchors/residuals ride its
                # checkpoints (a pre-attach load_state restores here too —
                # including the fresh strategy a mid-run kill+restore builds)
                attach(self._codec)
        if self._guard is not None:
            attach_g = getattr(strat, "attach_guard", None)
            if attach_g is not None and getattr(strat, "guard", None) is not self._guard:
                # the strategy adopts the guard: post-blend center checks
                # ride the fused ingest stats and every cluster grows a
                # last-known-good snapshot ring for rollback
                attach_g(self._guard)
        current = getattr(strat, "feedback_batch_fn", "missing")
        fleet_hook = current is not None and current != "missing" and getattr(
            current, "_fleet_hook", False
        )
        if self.client_backend != "fleet":
            if fleet_hook:
                strat.feedback_batch_fn = None
            return
        if self._fleet is None:
            from repro.fl.fleet import ClientFleet

            self._fleet = ClientFleet(list(self.clients.values()), template)
        if current == "missing":
            return
        # (re)install OUR fleet's hook — on every run start, not just fleet
        # construction, since another simulator sharing this strategy may
        # have rebound or cleared it in between. A caller-supplied batch fn
        # (no _fleet_hook tag) is always left alone.
        if current is None or (fleet_hook and getattr(current, "_fleet", None) is not self._fleet):
            fleet = self._fleet

            def hook(pairs):
                return fleet.feedback_many(pairs)

            hook._fleet_hook = True
            hook._fleet = fleet
            strat.feedback_batch_fn = hook

    def _set_model(self, c: SimClient, params: PyTree) -> None:
        """Install a downlinked model on a client (mirrored into the fleet's
        model row so the batched paths see it, and into the client's uplink
        anchor — a downlink is a value both sides agree on for free)."""
        c.model = params
        if self._codec is not None:
            self._codec.install(c.client_id, params)
        if self._fleet is not None:
            self._fleet.set_model(c.client_id, params)

    # ------------------------------------------------------ uplink encoding
    def _encode_upload(self, cid, new_params: PyTree) -> tuple[PyTree, int, int | None]:
        """Route ONE trained model through the uplink codec: returns the
        payload the strategy ingests, the billed wire bytes, and the dense
        size for ratio tracking. The client keeps its own uncompressed
        model; the server sees (and the predictor's change statistics see)
        the reconstruction — what actually crossed the compressed wire.
        With no codec this is the identity: dense params, dense bytes."""
        raw = model_bytes(new_params)
        if self._codec is None:
            return new_params, raw, None
        rec, nbytes = self._codec.encode(cid, new_params)
        return rec, nbytes, raw

    # -------------------------------------------------------- fault layer
    def _upload_with_faults(self, cid, nbytes: int, raw: int | None, t: float) -> tuple[float, bool]:
        """Bill one (possibly retried) upload: every failed attempt sends
        its full payload over the thin link (flagged retry-attributable
        past the first send) and waits a capped exponential backoff before
        re-sending. Returns ``(delay to arrival, delivered)``; the extra
        delay flows into version-based staleness accounting for free —
        the server simply sees an older base_version. ``delivered=False``
        only under the drop policy (the straggler baseline gives up)."""
        inj = self._faults
        fails, delivered = inj.upload_plan(cid)
        delay = 0.0
        for i in range(fails):
            delay += self.net.upload(nbytes, t + delay, raw_nbytes=raw, retry=i > 0)
            delay += inj.backoff(i)
        if not delivered:
            return delay, False
        dur = self.net.upload(nbytes, t + delay, raw_nbytes=raw, retry=fails > 0)
        if fails:
            inj.ledger["retry_delay_s"] += delay
        return delay + dur, True

    def _send_upload(self, push, t: float, cid, up_params, nbytes, raw, base_version) -> None:
        """Schedule one trained upload's arrival (+ fault retries, drops,
        duplicate deliveries). Payload carries the per-client send sequence
        number; the ingest side fences on it to absorb duplicates."""
        if self._faults is None:
            dur = self.net.upload(nbytes, t, raw_nbytes=raw)
            push(t + dur, "upload_done", (cid, up_params, base_version, 0))
            return
        delay, delivered = self._upload_with_faults(cid, nbytes, raw, t)
        if not delivered:  # drop policy hit the retry cap: straggler leaves
            self._retire_client(cid, "dropped")
            return
        pz = self._faults.poison(cid)
        if pz is not None:
            # value-level fault: the bytes crossed the wire fine, the
            # *values* arrive corrupt (bitflip / broken quantizer /
            # adversarial client). Both the original delivery and any
            # duplicate carry the same corrupted payload.
            from repro.fl.faults import apply_poison

            up_params = apply_poison(up_params, pz[0], pz[1], self._faults.cfg)
        useq = self._useq[cid] = self._useq.get(cid, 0) + 1
        push(t + delay, "upload_done", (cid, up_params, base_version, useq))
        dup = self._faults.duplicate(cid)
        if dup is not None:  # retransmission: real bytes cross the link again
            self.net.upload(nbytes, t, raw_nbytes=raw, retry=True)
            push(t + delay + dup, "upload_done", (cid, up_params, base_version, useq))

    def _push_downlink(self, push, t_send: float, dl, dur: float) -> None:
        """Schedule one downlink delivery. Under fault injection the send
        gets a per-recipient sequence number (the install path fences on
        it) and possibly an injected reorder delay."""
        if self._faults is None:
            push(t_send + dur, "downlink", dl)
            return
        dl._fseq = self._dl_seq[dl.client_id] = self._dl_seq.get(dl.client_id, -1) + 1
        push(t_send + dur + self._faults.reorder(dl.client_id), "downlink", dl)

    def _guard_check(self, cid, params) -> str:
        """Score ONE delivered upload against the ingest guard, BEFORE the
        strategy sees it. The cluster key is the client's current home (-1
        pre-assignment); the L1 distance stat is measured against that
        cluster's center — the discriminator that catches sign-flip poison,
        whose L2 norm is unchanged by construction. Rejected uploads never
        reach ``handle_upload``: aggregation, feedback and predictor
        learning are all skipped for free (bytes were billed at send
        time — the wire doesn't know the values are garbage)."""
        guard = self._guard
        cl = getattr(self.strategy, "clustering", None)
        home = cl.assignment.get(cid) if cl is not None else None
        if home is not None and home in cl.clusters:
            key, center = home, cl.clusters[home].center
        else:
            key, center = -1, None
        finite, l2, dist = guard.upload_stats(params, center)
        return guard.check_upload(cid, key, finite, l2, dist)

    def _retire_client(self, cid, kind: str) -> None:
        """Remove a permanently-dark client from the protocol: the server
        evicts it (freeing plane rows, reclaiming all-dark clusters) and
        the simulator stops scheduling it. Its accuracy freezes at the
        last installed model."""
        if cid in self._dead:
            return
        self._dead.add(cid)
        # the guard can retire clients without a fault injector in play
        led = self._faults.ledger if self._faults is not None else None
        if led is not None and kind == "dropped":
            led["dropped_clients"] += 1
        evict = getattr(self.strategy, "evict_clients", None)
        if evict is not None:
            res = evict([cid])
            if led is not None:
                led["evicted_clients"] += len(res["evicted"])
                led["reclaimed_clusters"] += len(res["reclaimed"])

    def _server_kill_restore(self) -> None:
        """Kill the live strategy mid-run and restore a fresh instance from
        a checkpoint written through the crash-safe checkpointer. The old
        object is discarded, so everything the continuation needs must come
        back through ``state_dict``/``load_state`` — the acceptance bar is
        that the run then finishes with the uninterrupted run's exact
        upload/byte/staleness ledger."""
        from repro.checkpoint.checkpointer import Checkpointer, latest_step, restore_pytree

        inj = self._faults
        plan = inj.plan.restart
        tree, meta = self.strategy.state_dict()
        ck = Checkpointer(plan.directory, keep=2)
        try:
            ck.save(inj.ledger["server_restarts"], tree, extra=meta)
        finally:
            ck.close()
        fresh = plan.strategy_factory()
        step = latest_step(plan.directory)
        path = os.path.join(plan.directory, f"step_{step:010d}")
        raw_meta = restore_pytree(path, like=None)[1]
        tree_r, meta_r = restore_pytree(path, like=fresh.state_template(raw_meta))
        fresh.load_state(tree_r, meta_r, client_id_type=plan.client_id_type)
        self.strategy = fresh
        if self._template is not None:
            # rebind the fleet's feedback hook and replay the codec state
            # into the restored strategy, exactly as a run start would
            self._ensure_fleet(self._template)
        inj.mark_restarted()

    # ----------------------------------------------------------- evaluation
    def _evaluate(self, t: float) -> float:
        accs = {}
        # a permanently-dark client was evicted server-side (model_for would
        # hand back init_params): it scores with its last installed model —
        # frozen, which is exactly the degradation the fault bench measures
        dead = self._dead
        if self._fleet is not None:
            # one masked launch for the whole fleet instead of N dispatches
            params = [
                self.clients[cid].model if cid in dead else self.strategy.model_for(cid)
                for cid in self._fleet.ids
            ]
            fleet_accs = self._fleet.evaluate_fleet(params)
            accs = {cid: float(a) for cid, a in zip(self._fleet.ids, fleet_accs)}
        else:
            for cid, c in self.clients.items():
                params = c.model if cid in dead else self.strategy.model_for(cid)
                accs[cid] = c.evaluate(params if params is not None else c.model)
        mean = float(np.mean(list(accs.values())))
        self.curve.append((t, mean))
        self._last_accs = accs
        return mean

    def _report(self, t_end: float, extra: dict) -> SimReport:
        if self._codec is not None:
            extra["uplink"] = {
                "mode": self._codec.mode,
                "payload_bytes": self._codec.nbytes,
                "launches": self._codec.launches,
            }
        self._evaluate(t_end)
        target_t = None
        for t, acc in self.curve:
            if acc >= self.target_acc:
                target_t = t
                break
        return SimReport(
            strategy=self.strategy.name,
            curve=self.curve,
            per_client_acc=self._last_accs,
            per_client_class={cid: c.device_class for cid, c in self.clients.items()},
            final_acc=self.curve[-1][1],
            time_to_target=target_t,
            up_bytes=self.net.up_bytes,
            down_bytes=self.net.down_bytes,
            up_events=self.net.up_events,
            down_events=self.net.down_events,
            peak_down=self.net.peak("down"),
            peak_up=self.net.peak("up"),
            duration=t_end,
            extra=extra,
            up_series=self.net.series("up"),
            down_series=self.net.series("down"),
            up_raw_bytes=self.net.up_raw_bytes,
            up_retry_bytes=self.net.up_retry_bytes,
        )

    # ------------------------------------------------------------ async run
    def _init_async_events(self, push) -> None:
        """Initial broadcast of the seed model + first tick — shared by the
        per-event and coalesced loops so their event streams start
        identically (the degenerate-window bitwise parity depends on it)."""
        strat = self.strategy
        init = strat.initial_models(sorted(self.clients))
        nbytes = model_bytes(next(iter(init.values())))
        self._template = next(iter(init.values()))
        self._ensure_fleet(self._template)
        if self._codec is not None:
            # both sides saw this broadcast: it is the delta anchor
            self._codec.seed(init)
        for cid, params in init.items():
            dl = self.net.download(nbytes, 0.0)
            c = self.clients[cid]
            self._set_model(c, params)
            c.base_version = 0
            push(dl + c.compute_time(), "upload_start", cid)
        if getattr(strat, "tick_interval", None):
            push(strat.tick_interval, "tick", None)

    def run_async(self, *, max_time: float = 3600.0, max_uploads: int | None = None) -> SimReport:
        """Event loop for asynchronous strategies (EchoPFL, FedAsyn, FedSEA).

        With a coalescing window (``REPRO_ASYNC_COALESCE`` / the
        ``coalesce_window`` constructor argument), all events inside one
        virtual-time window are popped together and processed as
        kind-batched launches (:meth:`_run_async_coalesced`); the default
        (window 0) is this per-event loop, byte-for-byte the parity
        baseline."""
        if self.coalesce_window > 0:
            return self._run_async_coalesced(
                self.coalesce_window, max_time=max_time, max_uploads=max_uploads
            )
        strat = self.strategy
        events: list = []  # (time, seq, kind, payload)

        def push(t, kind, payload):
            heapq.heappush(events, (t, next(self._counter), kind, payload))

        self._init_async_events(push)
        next_eval = self.eval_interval
        uploads = 0
        t = 0.0
        while events:
            if self._faults is not None and self._faults.restart_due(uploads):
                self._server_kill_restore()
                strat = self.strategy
            t, _, kind, payload = heapq.heappop(events)
            if t > max_time:
                t = max_time
                break
            while t >= next_eval:
                self._evaluate(next_eval)
                next_eval += self.eval_interval

            if kind == "upload_start":  # local training finished; uplink begins
                cid = payload
                t_on = self._next_online(cid, t)
                if t_on == math.inf:  # crash was fatal: device never returns
                    self._retire_client(cid, "death")
                    continue
                if t_on > t:  # device offline: resume when it rejoins
                    push(t_on + self.clients[cid].compute_time(), "upload_start", cid)
                    continue
                c = self.clients[cid]
                if self._fleet is not None:
                    # row-sliced fleet path: trains from (and writes back)
                    # this client's model row; c.model mirrors the result
                    new_params, _ = self._fleet.train_client(cid)
                else:
                    new_params, _ = c.local_train()
                c.model = new_params
                up_params, nbytes, raw = self._encode_upload(cid, new_params)
                self._send_upload(push, t, cid, up_params, nbytes, raw, c.base_version)
            elif kind == "upload_done":
                cid, params, base_version, useq = payload
                if self._faults is not None:
                    # version-fenced idempotent ingest: a duplicate delivery
                    # (or anything older than what already landed) is absorbed
                    if useq <= self._ingest_high.get(cid, -1):
                        self._faults.ledger["dups_absorbed"] += 1
                        continue
                    self._ingest_high[cid] = useq
                if self._guard is not None and self._guard_check(cid, params) != "accept":
                    # quarantined at ingest: the strategy never sees the
                    # payload; the client (unless escalated to eviction)
                    # keeps training from its own current model
                    if self._guard.should_evict(cid):
                        self._retire_client(cid, "guard")
                    else:
                        push(t + self.clients[cid].compute_time(), "upload_start", cid)
                    continue
                uploads += 1
                c = self.clients[cid]
                downlinks = strat.handle_upload(cid, params, base_version, c.data.n, t)
                # sync-point strategies may buffer; flush anything returned
                for dl in downlinks:
                    dur = self.net.download(model_bytes(dl.params), t)
                    self._push_downlink(push, t, dl, dur)
                # client starts next local round immediately from current base
                push(t + self.clients[cid].compute_time(), "upload_start", cid)
                if max_uploads and uploads >= max_uploads:
                    break
            elif kind == "downlink":
                dl = payload
                if self._faults is not None:
                    # reorder fence: a delivery overtaken by a newer send to
                    # the same client must not roll its model back
                    if dl._fseq < self._dl_high.get(dl.client_id, -1):
                        self._faults.ledger["stale_downlinks_absorbed"] += 1
                        continue
                    self._dl_high[dl.client_id] = dl._fseq
                c = self.clients[dl.client_id]
                self._set_model(c, dl.params)
                c.base_version = dl.version
                c.cluster_id = dl.cluster_id
                if hasattr(strat, "clustering") and dl.cluster_id in strat.clustering.clusters:
                    c.partial_finetune = (
                        dl.client_id in strat.clustering.clusters[dl.cluster_id].partial_finetune
                    )
            elif kind == "tick":  # strategy-driven periodic hook (FedSEA sync points)
                for dl in strat.on_tick(t):
                    dur = self.net.download(model_bytes(dl.params), t)
                    self._push_downlink(push, t, dl, dur)
                if strat.tick_interval:
                    push(t + strat.tick_interval, "tick", None)

        extra = strat.stats() if hasattr(strat, "stats") else {}
        extra["uploads"] = uploads
        if self.churn:
            extra["churn_delays"] = self.churn_delays
        if self._faults is not None:
            extra["faults"] = self._faults.ledger_snapshot()
        if self._guard is not None:
            extra["guard"] = self._guard.ledger_snapshot()
        return self._report(t, extra)

    # ------------------------------------------------- coalesced async run
    def _run_async_coalesced(
        self, window: float, *, max_time: float, max_uploads: int | None
    ) -> SimReport:
        """Event-coalesced async loop: the paper's "aggregate as updates
        arrive" server, without paying one Python/jit dispatch cycle per
        arrival. All events whose virtual times fall in one ``window`` are
        popped together and bucketed by kind, and each bucket is ONE
        batched operation: N ``downlink`` events one staged model write, N
        ``upload_start`` events one row-sliced fleet training launch, N
        ``upload_done`` events one :meth:`EchoPFLServer.handle_uploads`
        ingest (phase order downlink -> train -> ingest, the causal order
        of one server tick). Every event keeps its own timestamp for
        billing and follow-up scheduling, events inside a bucket process in
        event order, and a window never crosses an eval tick, a strategy
        tick, the horizon, or the upload cap.

        Semantics: a window is one superstep of concurrently-arriving
        events — messages *generated* inside it (an ingest's downlinks, a
        training's arrival) deliver when their own timestamps pop, i.e. at
        the next window. The per-event loop is the ``window -> 0`` limit:
        with one event per window the phases are trivially the per-event
        order and the trajectories are bitwise-identical (the parity suite
        asserts exactly this, on both kernel backends); at real windows the
        virtual-time trajectory and per-upload billing are unchanged while
        model values stay allclose — concurrent devices simply no longer
        see downlinks that landed mid-window retroactively rebasing the
        training round they had already finished. Compute times draw from
        the shared device RNG at collection time, in global event order,
        so the draw stream matches the per-event loop's except where churn
        interleaves a resume with an arrival that was *generated* in the
        same window (delivered next superstep): only then can virtual
        times shift."""
        strat = self.strategy
        events: list = []  # (time, seq, kind, payload)

        def push(t, kind, payload):
            heapq.heappush(events, (t, next(self._counter), kind, payload))

        self._init_async_events(push)
        self.coalesced_groups = {}  # fresh introspection per run

        def stash(tn, kn, pn):
            """Draw from the shared device RNG at COLLECTION time, in global
            event order: churn resumes (upload_start) and next-round
            schedules (upload_done) both call ``compute_time`` on the one
            generator every client's ``round_time_fn`` closes over, and the
            phase processing below reorders events by kind — drawing there
            would permute the stream relative to the per-event loop. The
            pre-drawn values ride the bucket entries."""
            if kn == "upload_start":
                t_on = self._next_online(pn, tn)
                if t_on == math.inf:  # fatal crash: no resume, no RNG draw
                    return math.inf
                if t_on > tn:  # device offline: resume when it rejoins
                    return t_on + self.clients[pn].compute_time()
                return None
            if kn == "upload_done":
                if self._faults is not None:
                    # duplicate fence at collection time: the per-event loop
                    # fences at pop time, which is this same global order —
                    # and an absorbed duplicate must not draw compute time
                    if pn[3] <= self._ingest_high.get(pn[0], -1):
                        self._faults.ledger["dups_absorbed"] += 1
                        return "dup"
                    self._ingest_high[pn[0]] = pn[3]
                if self._guard is not None:
                    # guard verdicts, like the dup fence, land at collection
                    # time in global event order — the per-event loop decides
                    # at pop time, which is this same order. An evicted
                    # client never resumes, so (like a fatal crash) it must
                    # not draw a compute time; a rejected-but-alive client
                    # draws exactly one, for its rescheduled next round.
                    if self._guard_check(pn[0], pn[1]) != "accept":
                        if self._guard.should_evict(pn[0]):
                            self._retire_client(pn[0], "guard")
                            return "evicted"
                        return ("rejected", self.clients[pn[0]].compute_time())
                return self.clients[pn[0]].compute_time()
            return None

        next_eval = self.eval_interval
        uploads = 0
        superstep = 0
        t = 0.0
        while events:
            if self._faults is not None and self._faults.restart_due(uploads):
                self._server_kill_restore()
                strat = self.strategy
            t0, _, kind, payload = heapq.heappop(events)
            if t0 > max_time:
                t = max_time
                break
            t = t0
            while t >= next_eval:
                self._evaluate(next_eval)
                next_eval += self.eval_interval

            if kind == "tick":  # strategy-driven periodic hook (FedSEA sync points)
                for dl in strat.on_tick(t):
                    dur = self.net.download(model_bytes(dl.params), t)
                    self._push_downlink(push, t, dl, dur)
                if strat.tick_interval:
                    push(t + strat.tick_interval, "tick", None)
                continue

            superstep += 1
            with span("superstep", superstep=superstep) as step:
                # collect the window and bucket by kind (time order within each)
                with span("collect"):
                    buckets: dict[str, list] = {"downlink": [], "upload_start": [], "upload_done": []}
                    s0 = stash(t0, kind, payload)
                    buckets[kind].append((t0, payload, s0))
                    limit = t0 + window
                    cap = max_uploads - uploads if max_uploads else None
                    # the cap counts ACCEPTED ingests only: dup-fenced, guard-rejected
                    # and guard-evicted arrivals never reach the server (a pre-drawn
                    # float compute time marks an arrival that will actually ingest)
                    ud_seen = 1 if kind == "upload_done" and isinstance(s0, float) else 0
                    while events and (cap is None or ud_seen < cap):
                        tn, _, kn, pn = events[0]
                        if kn == "tick" or tn >= limit or tn >= next_eval or tn > max_time:
                            break
                        heapq.heappop(events)
                        sn = stash(tn, kn, pn)
                        buckets[kn].append((tn, pn, sn))
                        t = tn
                        ud_seen += kn == "upload_done" and isinstance(sn, float)
                    for kn, group in buckets.items():
                        if group:
                            self.coalesced_groups.setdefault(kn, []).append(len(group))
                if tracing.on():
                    step.set_metadata(
                        downlinks=sum(len(p) if isinstance(p, list) else 1 for _, p, _ in buckets["downlink"]),
                        starts=len(buckets["upload_start"]),
                        uploads=len(buckets["upload_done"]),
                    )

                if buckets["downlink"]:
                    self._coalesced_downlinks(buckets["downlink"])
                if buckets["upload_start"]:
                    self._coalesced_upload_starts(buckets["upload_start"], push)
                if buckets["upload_done"]:
                    uploads += self._coalesced_upload_dones(buckets["upload_done"], push)
                    if max_uploads and uploads >= max_uploads:
                        break

        extra = strat.stats() if hasattr(strat, "stats") else {}
        extra["uploads"] = uploads
        extra["coalesce_window"] = window
        if self.churn:
            extra["churn_delays"] = self.churn_delays
        if self._faults is not None:
            extra["faults"] = self._faults.ledger_snapshot()
        if self._guard is not None:
            extra["guard"] = self._guard.ledger_snapshot()
        return self._report(t, extra)

    def _coalesced_upload_starts(self, group, push) -> None:
        """One fused training launch for a window of concurrently finishing
        local rounds (churn settled — and its RNG drawn — at collection
        time); billing and scheduling run per event in order, so heap
        tie-breaking sequence numbers match the per-event loop push for
        push."""
        ready = [cid for _, cid, resume in group if resume is None]
        trained: dict[Any, Any] = {}
        encoded: dict[Any, Any] = {}
        if self._fleet is not None and len(ready) > 1:
            with span("train", rows=len(ready)):
                if self._codec is not None:
                    # the window's whole cohort compresses as ONE codec launch,
                    # fed the training launch's device matrix directly (no
                    # per-client re-flatten round trip)
                    outs, _, vecs = self._fleet.train_rows(ready, with_vecs=True)
                    recs, _ = self._codec.encode_rows(ready, vecs)
                    encoded = dict(zip(ready, recs))
                else:
                    outs, _ = self._fleet.train_rows(ready)
            trained = dict(zip(ready, outs))
        for cid in ready:
            if cid not in trained:
                with span("train", rows=1):
                    if self._fleet is not None:
                        trained[cid], _ = self._fleet.train_client(cid)
                    else:
                        trained[cid], _ = self.clients[cid].local_train()
        with span("bill"):
            for ti, cid, resume in group:
                if resume == math.inf:  # fatal crash: the device never returns
                    self._retire_client(cid, "death")
                    continue
                if resume is not None:  # device was offline: resumes when back
                    push(resume, "upload_start", cid)
                    continue
                c = self.clients[cid]
                new_params = c.model = trained[cid]
                if cid in encoded:
                    up_params, nbytes, raw = encoded[cid], self._codec.nbytes, model_bytes(new_params)
                else:
                    up_params, nbytes, raw = self._encode_upload(cid, new_params)
                self._send_upload(push, ti, cid, up_params, nbytes, raw, c.base_version)

    def _coalesced_upload_dones(self, group, push) -> int:
        """One batched server ingest for a window of arrivals; downlinks
        and the next local rounds are billed/scheduled per event, in order."""
        strat = self.strategy
        # duplicate, guard-rejected and guard-evicted deliveries were fenced
        # out at collection time: they never reach the server and never
        # ingest. A rejected-but-alive client still gets its next round
        # scheduled (its compute time rode the bucket entry as a tuple);
        # dups and evictions schedule nothing and drew nothing.
        live = [e for e in group if isinstance(e[2], float)]
        batch = [
            (cid, params, bv, self.clients[cid].data.n, ti)
            for ti, (cid, params, bv, _useq), _ in live
        ]
        downlinks_per = []
        if batch:
            with span("ingest", uploads=len(batch)):
                if len(batch) > 1 and hasattr(strat, "handle_uploads"):
                    downlinks_per = strat.handle_uploads(batch)
                else:
                    with span("ingest/single", reason="one" if len(batch) == 1 else "unbatched"):
                        downlinks_per = [strat.handle_upload(*b) for b in batch]
        with span("bill"):
            dls_iter = iter(downlinks_per)
            for ti, (cid, _params, _bv, _useq), sn in group:
                if sn == "dup" or sn == "evicted":
                    continue
                if isinstance(sn, tuple):  # guard-rejected: reschedule only
                    push(ti + sn[1], "upload_start", cid)
                    continue
                next_compute = sn
                dls = next(dls_iter)
                if self._faults is not None:
                    # fault mode bills and ships each downlink individually so
                    # sequence numbers and injected reorder delays land exactly
                    # as the per-event loop's (byte totals and event counts are
                    # identical to the bulk billing either way)
                    for dl in dls:
                        dur = self.net.download(model_bytes(dl.params), ti)
                        self._push_downlink(push, ti, dl, dur)
                    push(ti + next_compute, "upload_start", cid)
                    continue
                # every downlink of one ingest carries a whole model (unicast
                # and echo broadcast alike), so the fan-out shares one wire
                # size and one transfer duration: bill it in one call and ship
                # it as ONE batch event instead of len(fan-out) heap entries —
                # the per-downlink Python (push/pop/billing) is what dominates
                # the echo at fleet scale
                run: list = []
                run_obj, run_nb = None, 0
                for dl in dls:
                    if run and dl.params is not run_obj:  # a broadcast fans one object
                        nb = model_bytes(dl.params)
                        if nb != run_nb:
                            dur = self.net.download_bulk(run_nb, len(run), ti)
                            push(ti + dur, "downlink", run)
                            run = []
                        run_obj, run_nb = dl.params, nb
                    elif not run:
                        run_obj, run_nb = dl.params, model_bytes(dl.params)
                    run.append(dl)
                if run:
                    dur = self.net.download_bulk(run_nb, len(run), ti)
                    push(ti + dur, "downlink", run)
                # next local round: duration pre-drawn at collection time
                push(ti + next_compute, "upload_start", cid)
        return len(batch)

    def _coalesced_downlinks(self, group) -> None:
        """Apply a window of downlinks (payloads may be single
        :class:`Downlink` objects or whole fan-out batches): the fleet's
        model rows take one staged batch write, client protocol state
        updates per downlink in delivery order."""
        strat = self.strategy
        flat: list = []
        for _ti, payload, _ in group:
            flat.extend(payload) if isinstance(payload, list) else flat.append(payload)
        if self._faults is not None:
            # reorder fence in delivery order, BEFORE the staged batch write:
            # a stale delivery must not reach the model rows at all
            keep: list = []
            for dl in flat:
                if dl._fseq < self._dl_high.get(dl.client_id, -1):
                    self._faults.ledger["stale_downlinks_absorbed"] += 1
                    continue
                self._dl_high[dl.client_id] = dl._fseq
                keep.append(dl)
            flat = keep
            if not flat:
                return
        batched_rows = self._fleet is not None and len(flat) > 1
        if batched_rows:
            self._fleet.set_models(
                [dl.client_id for dl in flat], [dl.params for dl in flat]
            )
        has_clustering = hasattr(strat, "clustering")
        for dl in flat:
            c = self.clients[dl.client_id]
            if batched_rows:
                c.model = dl.params  # row already staged by set_models
                if self._codec is not None:  # anchors refresh per delivery
                    self._codec.install(dl.client_id, dl.params)
            else:
                self._set_model(c, dl.params)
            c.base_version = dl.version
            c.cluster_id = dl.cluster_id
            if has_clustering and dl.cluster_id in strat.clustering.clusters:
                c.partial_finetune = (
                    dl.client_id in strat.clustering.clusters[dl.cluster_id].partial_finetune
                )

    # ------------------------------------------------------------- sync run
    def run_sync(self, *, rounds: int = 50, max_time: float | None = None) -> SimReport:
        """Round-barrier loop for synchronous strategies (FedAvg, Oort,
        ClusterFL with per-cluster barriers, Standalone)."""
        strat = self.strategy
        init = strat.initial_models(sorted(self.clients))
        nbytes = model_bytes(next(iter(init.values())))
        self._template = next(iter(init.values()))
        self._ensure_fleet(self._template)
        t = 0.0
        if self._codec is not None:
            self._codec.seed(init)
        for cid, params in init.items():
            self._set_model(self.clients[cid], params)
        t += nbytes / self.net.downstream_bps
        self.net.download(nbytes * len(init), 0.0)

        next_eval = self.eval_interval
        groups_time = {g: t for g in strat.groups(sorted(self.clients))}
        rounds_done = 0  # rounds=0 must return a zero-round report, not crash
        for rnd in range(rounds):
            # each group (one global group, or one per cluster) runs its own barrier
            for group_id, members in strat.groups(sorted(self.clients)).items():
                t0 = groups_time.get(group_id, t)
                selected = strat.select(group_id, members, rnd)
                if not selected:
                    continue
                finish_times = {}
                uploads = {}
                encoded: dict[Any, Any] = {}
                if self._fleet is not None:
                    # the whole cohort's local training is ONE fused launch;
                    # per-client timing/accounting below stays loop-ordered
                    # so the RNG draws and byte counts match the loop path
                    if self._codec is not None:
                        trained, _, vecs = self._fleet.train_cohort(
                            selected, [strat.model_for(cid) for cid in selected],
                            with_vecs=True,
                        )
                        recs, _ = self._codec.encode_rows(selected, vecs)
                        encoded = dict(zip(selected, recs))
                    else:
                        trained, _ = self._fleet.train_cohort(
                            selected, [strat.model_for(cid) for cid in selected]
                        )
                    trained = dict(zip(selected, trained))
                for cid in selected:
                    c = self.clients[cid]
                    if self._fleet is not None:
                        params = trained[cid]
                    else:
                        params, _ = c.local_train(strat.model_for(cid))
                    dur = c.compute_time()
                    if cid in encoded:
                        up_params, nbytes_up, raw = (
                            encoded[cid], self._codec.nbytes, model_bytes(params),
                        )
                    else:
                        up_params, nbytes_up, raw = self._encode_upload(cid, params)
                    up_dur = self.net.upload(nbytes_up, t0 + dur, raw_nbytes=raw)
                    finish_times[cid] = t0 + dur + up_dur
                    uploads[cid] = up_params
                barrier = max(finish_times.values())
                downlinks = strat.finish_round(group_id, uploads, barrier)
                dl_time = 0.0
                for dl in downlinks:
                    dl_time = max(dl_time, self.net.download(model_bytes(dl.params), barrier))
                    c = self.clients[dl.client_id]
                    self._set_model(c, dl.params)
                    c.base_version = dl.version
                groups_time[group_id] = barrier + dl_time
            t = max(groups_time.values())
            rounds_done = rnd + 1
            while t >= next_eval:
                self._evaluate(next_eval)
                next_eval += self.eval_interval
            if max_time and t > max_time:
                break
        extra = strat.stats() if hasattr(strat, "stats") else {}
        extra["rounds"] = rounds_done
        return self._report(t, extra)

    def run(self, **kw) -> SimReport:
        if getattr(self.strategy, "is_synchronous", False):
            return self.run_sync(**{k: v for k, v in kw.items() if k in ("rounds", "max_time")})
        return self.run_async(**{k: v for k, v in kw.items() if k in ("max_time", "max_uploads")})
