"""FedAsyn [Xie et al. 2019]: fully asynchronous single global model with
polynomial staleness weight decay — the decay is exactly what EchoPFL
rejects (it discounts slow devices' knowledge; Challenge #2).

The seed implementation blended per-leaf pytrees in a per-upload Python
loop — O(leaves) dispatches per arrival, and no batched ingest at all, so
comm-cost head-to-heads against the fleet-batched EchoPFL path were really
measuring Python overhead. This port keeps the global model as ONE flat
f32 vector (the same layout the parameter plane and the client fleet use)
and ingests a coalesced window of arrivals as one ``lax.scan`` chain
launch (:func:`_lerp_chain`) with a single device_get for the window's
unicast downlinks.

Bitwise discipline: both the per-event blend and the scan body emit the
canonical fenced two-op expression (see ``plane.lerp_vec``) with the
staleness-decayed weight as a *traced* f32 operand — the weight itself is
computed in exact host float64 and cast once, so per-event and coalesced
trajectories are bitwise-identical (the parity tests pin this).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytrees import flatten_spec
from repro.common.tracing import fetch
from repro.core.server import Downlink
from repro.core.staleness import StalenessTracker

PyTree = Any


@jax.jit
def _lerp_dyn(v, u, t):
    # dynamic-t variant of plane.lerp_vec: the same fenced two-op blend, but
    # with the per-upload weight traced (one compiled launch for every
    # staleness level instead of one jit cache entry per weight)
    m1, m2 = jax.lax.optimization_barrier(((1.0 - t) * v, t * u))
    return m1 + m2


@jax.jit
def _lerp_chain(v0, us, ts):
    # sequential-equivalent window ingest: scan the fenced blend over the
    # arrivals in event order, emitting every intermediate model (the
    # per-upload unicast downlink payloads) plus the final carry
    def step(v, ut):
        u, t = ut
        m1, m2 = jax.lax.optimization_barrier(((1.0 - t) * v, t * u))
        v2 = m1 + m2
        return v2, v2

    return jax.lax.scan(step, v0, (us, ts))


class FedAsyn:
    name = "fedasyn"
    is_synchronous = False

    def __init__(self, init_params: PyTree, *, alpha: float = 0.6, decay_power: float = 0.5):
        self.spec = flatten_spec(init_params)
        self._vec = self.spec.flatten(init_params)
        self.alpha = alpha
        self.decay_power = decay_power
        self.version = 0
        self.staleness = StalenessTracker()
        self._view: tuple[int, PyTree] = (0, init_params)  # (version, pytree) cache

    @property
    def global_model(self) -> PyTree:
        """Current global model as a pytree — version-cached, so repeat
        reads between ingests (every client's ``model_for`` at an eval
        tick) share one unflatten AND one object identity (what the fleet's
        eval-row cache and the simulator's broadcast run-coalescing key on)."""
        if self._view[0] != self.version:
            self._view = (self.version, self.spec.unflatten(self._vec))
        return self._view[1]

    def initial_models(self, client_ids):
        return {cid: self.global_model for cid in client_ids}

    def model_for(self, client_id):
        return self.global_model

    def _weight(self, base_version: int, version: int) -> np.float32:
        staleness = max(0, version - base_version)
        self.staleness.record(staleness)
        # stale updates decayed; exact host float64, one f32 cast, so the
        # per-event and chain launches consume the identical operand
        return np.float32(self.alpha * (1.0 + staleness) ** (-self.decay_power))

    def handle_upload(self, client_id, params, base_version, n_samples, t):
        w = self._weight(base_version, self.version)
        self._vec = _lerp_dyn(self._vec, self.spec.flatten(params), w)
        self.version += 1
        return [Downlink(client_id, self.global_model, self.version, 0, "unicast")]

    def handle_uploads(self, batch: list[tuple]) -> list[list[Downlink]]:
        """Batched ingest for a coalesced window of arrivals: one fused scan
        of the sequential blends (bitwise the per-event chain), one
        device_get, and the per-upload downlink models fan out as numpy
        views over the window's stacked result."""
        # each in-window arrival sees the version as bumped by the arrivals
        # before it — exactly what sequential handle_upload calls would do
        ws = np.stack([
            self._weight(bv, self.version + j)
            for j, (_, _, bv, _, _) in enumerate(batch)
        ])
        us = jnp.stack([self.spec.flatten(p) for _, p, _, _, _ in batch])
        self._vec, models = _lerp_chain(self._vec, us, ws)
        models_np = np.asarray(fetch(models, "chain"))
        models_np.flags.writeable = False  # leaves are views: freeze
        out = []
        for j, (cid, _p, _bv, _n, _t) in enumerate(batch):
            self.version += 1
            self._view = (self.version, self.spec.unflatten_np(models_np[j]))
            out.append([Downlink(cid, self._view[1], self.version, 0, "unicast")])
        return out

    def stats(self):
        return {"version": self.version, "staleness": self.staleness.snapshot()}
