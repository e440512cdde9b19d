"""Data-aware dynamic client clustering (paper Sec. 4).

Three mechanisms:
  * on-arrival initial assignment (Sec. 4.2): the first C arrivals seed the
    centers; later arrivals go to the nearest center by L1 parameter
    distance (Eq. 1) — computed by the Pallas streaming kernel on TPU.
  * feedback (Sec. 4.3.1): chi-squared(F_pred, F_true) x Var(S_soft)
    (Eq. 2/3) de-confounds clustering error from training stage.
  * refinement (Sec. 4.3.2/4.3.3): merging via Algorithm-1 optimization-
    direction attention; expansion peels the worst-feedback 20% of a cluster
    into a new cluster seeded by transfer from the old center, whose members
    do head-only fine-tuning until the next merge.

Two storage backends, selected by ``REPRO_PLANE`` (or the ``backend``
argument): ``plane`` (default) keeps every center and broadcast anchor as a
row of a device-resident :class:`~repro.core.plane.ParameterPlane`, so the
hot path — assignment distances, the mixed-rate blend, merge candidate
search — runs on stacked flat matrices with no per-upload pytree
flattening; ``pytree`` is the original per-cluster-pytree path, kept
bit-compatible for parity testing and as the benchmark baseline. Both
backends apply identical fp32 arithmetic, so cluster assignments match
exactly.

The plane backend can additionally shard its row store over a device mesh
(``REPRO_PLANE_MESH`` knob, or an explicit ``mesh`` argument): the batched
kernels then run per row-shard with cross-shard reductions only at the
argmin/segment-sum points (see kernels/plane_sharded.py). Per-row
arithmetic is unchanged, so sharded and single-device planes take
identical assignment/merge decisions on the same upload stream.
"""
from __future__ import annotations

import os
from typing import Any, Callable

import jax.numpy as jnp
import numpy as np

from repro.common.pytrees import tree_flat_vector, tree_lerp, tree_unflatten_vector
from repro.common.tracing import fetch
from repro.core.plane import ParameterPlane
from repro.kernels import ops as K

PyTree = Any


def default_backend() -> str:
    return os.environ.get("REPRO_PLANE", "plane").lower()


class Cluster:
    """One cluster branch. ``center`` and ``last_broadcast_center`` are live
    pytree views; in plane mode they materialize on demand from plane rows
    (cached until the row changes), so the matrices stay device-resident
    and pytrees only exist at protocol boundaries."""

    def __init__(
        self,
        cluster_id: int,
        center: PyTree | None = None,
        *,
        plane: ParameterPlane | None = None,
        row: int | None = None,
        bcast_row: int | None = None,
    ):
        self.cluster_id = cluster_id
        self.version = 0  # bumped on every aggregation into this cluster
        self.members: set = set()
        self.partial_finetune: set = set()  # expansion mode clients
        self.pf_round = -1  # refine round in which partial_finetune was imposed
        self.last_broadcast_version = 0
        self._plane = plane
        self._row = row
        self._bcast_row = bcast_row
        self._center_cache: PyTree | None = None
        self._bcast_cache: PyTree | None = None
        self._center_tree: PyTree | None = center if plane is None else None
        self._bcast_tree: PyTree | None = None
        # last-known-good snapshot ring (ingest-guard rollback): plane rows
        # or pytrees, written at broadcast time, consumed by rollback()
        self._snap_rows: list[int] | None = None
        self._snap_trees: list[PyTree | None] | None = None
        self._snap_cursor = 0
        self._snap_count = 0

    @property
    def size(self) -> int:
        return len(self.members)

    # --------------------------------------------------------- pytree views
    @property
    def center(self) -> PyTree:
        if self._plane is None:
            return self._center_tree
        if self._center_cache is None:
            self._center_cache = self._plane.to_pytree(self._row)
        return self._center_cache

    @center.setter
    def center(self, value: PyTree) -> None:
        if self._plane is None:
            self._center_tree = value
        else:
            self._plane.write(self._row, value)
            self._center_cache = None

    @property
    def last_broadcast_center(self) -> PyTree:
        if self._plane is None:
            return self._bcast_tree
        if self._bcast_cache is None:
            self._bcast_cache = self._plane.to_pytree(self._bcast_row)
        return self._bcast_cache

    @last_broadcast_center.setter
    def last_broadcast_center(self, value: PyTree) -> None:
        if self._plane is None:
            self._bcast_tree = value
        else:
            self._plane.write(self._bcast_row, value)
            self._bcast_cache = None

    # ----------------------------------------------------- plane-mode views
    @property
    def center_vec(self):
        """Flat center vector (plane mode): a device row, no tree traversal."""
        return self._plane.row(self._row)

    @property
    def broadcast_vec(self):
        return self._plane.row(self._bcast_row)

    def set_center_vec(self, vec) -> None:
        self._plane.write(self._row, vec)
        self._center_cache = None

    def snapshot_broadcast(self) -> None:
        """Record the current center as the broadcast anchor (row copy in
        plane mode — the center pytree is never materialized for this).
        With a snapshot ring attached the broadcast moment also files the
        center as a last-known-good rollback point: a center only reaches
        here after passing the guard's post-blend check, so the ring holds
        exactly the states the defense layer is willing to return to."""
        if self._plane is None:
            self._bcast_tree = self._center_tree
        else:
            self._plane.copy_row(self._row, self._bcast_row)
            self._bcast_cache = None
        self._push_snapshot()

    # ------------------------------------------------- guard snapshot ring
    def ensure_snapshot_ring(self, depth: int) -> None:
        """Allocate the last-known-good ring (idempotent; guard attach may
        retrofit rings onto clusters restored from a checkpoint)."""
        if depth <= 0 or self._snap_rows is not None or self._snap_trees is not None:
            return
        if self._plane is not None:
            self._snap_rows = [self._plane.alloc() for _ in range(depth)]
        else:
            self._snap_trees = [None] * depth
        self._snap_cursor = 0
        self._snap_count = 0

    def _push_snapshot(self) -> None:
        ring = self._snap_rows if self._plane is not None else self._snap_trees
        if ring is None:
            return
        if self._plane is not None:
            self._plane.copy_row(self._row, self._snap_rows[self._snap_cursor])
        else:
            self._snap_trees[self._snap_cursor] = self._center_tree
        self._snap_cursor = (self._snap_cursor + 1) % len(ring)
        self._snap_count = min(self._snap_count + 1, len(ring))

    def rollback(self) -> bool:
        """Restore the center from the newest *finite* ring entry (newest
        to oldest, then the broadcast anchor as the final fallback — every
        cluster has one from birth, so late detection can always recover
        unless every recorded state is itself corrupt). Returns whether a
        restore happened; the caller bumps the version, records the event
        on the CI branch, and re-broadcasts on demand."""
        candidates: list[Any] = []
        ring = self._snap_rows if self._plane is not None else self._snap_trees
        if ring is not None and self._snap_count:
            n = len(ring)
            for back in range(1, self._snap_count + 1):
                candidates.append(ring[(self._snap_cursor - back) % n])
        candidates.append(self._bcast_row if self._plane is not None else self._bcast_tree)
        for cand in candidates:
            if self._plane is not None:
                if not bool(np.isfinite(fetch(self._plane.row(cand), "rollback")).all()):
                    continue  # this snapshot is itself corrupt: go older
                self._plane.copy_row(cand, self._row)
                self._center_cache = None
            else:
                if cand is None or not bool(
                    np.isfinite(fetch(tree_flat_vector(cand), "rollback")).all()
                ):
                    continue
                self._center_tree = cand
            return True
        return False

    def release(self) -> None:
        """Return this cluster's plane rows to the free list."""
        if self._plane is not None:
            self._plane.free(self._row)
            self._plane.free(self._bcast_row)
            for r in self._snap_rows or ():
                self._plane.free(r)


class DynamicClustering:
    """Server-side cluster registry with incremental init + refinement."""

    def __init__(
        self,
        num_initial: int,
        mix_rate: float = 0.5,
        hm: float = 2.0,
        backend: str | None = None,
        mesh: Any | None = None,
    ):
        self.num_initial = num_initial
        self.mix_rate = mix_rate
        self.hm = hm  # merge trigger: merge when count >= hm * num_initial
        self.backend = (backend or default_backend()).lower()
        if self.backend not in ("plane", "pytree"):
            raise ValueError(f"REPRO_PLANE backend must be plane|pytree, got {self.backend}")
        # mesh=None defers to the REPRO_PLANE_MESH env knob; mesh=False
        # forces the single-device plane even when the knob is set (the
        # benchmark baseline must not silently go sharded under ci.sh env)
        if mesh is False:
            mesh = None
        elif mesh is None and self.backend == "plane":
            from repro.launch.mesh import plane_mesh_from_env

            mesh = plane_mesh_from_env()  # default None: single-device plane
        self.mesh = mesh if self.backend == "plane" else None
        # Below this many batched rows the collectives cost more than they
        # save and one device runs the launch faster — the row *store* stays
        # sharded either way (that is the memory win); only compute
        # placement adapts. 0 forces sharded compute (parity tests).
        self.mesh_min_rows = int(os.environ.get("REPRO_PLANE_MESH_MIN_ROWS", "128"))
        self.plane: ParameterPlane | None = None  # built from the first center's structure
        # >0 when an ingest guard is attached: every cluster carries that
        # many last-known-good snapshot rows for center rollback. 0 (the
        # default) allocates nothing — guard-off pays nothing.
        self.snapshot_ring = 0
        self.clusters: dict[int, Cluster] = {}
        self._next_id = 0
        self.assignment: dict[Any, int] = {}
        self.merges = 0
        self.expansions = 0
        self.peel_counts: dict[Any, int] = {}  # anti-churn: cap per-client peels
        self._last_expand_round: dict[int, int] = {}
        # assign-time flatten + fused blend, reused by the same upload's
        # aggregate call: (update object, argmin cluster, u vec, blended vec,
        # center version the blend was computed from). The update itself is
        # held (not its id()) so a recycled object address can never alias a
        # stale cache entry.
        self._pending: tuple[Any, int | None, Any, Any, int] | None = None

    # ------------------------------------------------------------------ init
    def _ensure_plane(self, template: PyTree) -> None:
        if self.backend == "plane" and self.plane is None:
            self.plane = ParameterPlane(
                template, capacity=max(8, 4 * self.num_initial), mesh=self.mesh
            )

    def _kernel_mesh_kwargs(self, nrows: int) -> dict:
        """Static mesh kwargs for a batched kernel launch over ``nrows``
        sharded rows. Empty when the plane is unsharded — or when the batch
        is too small to amortize the cross-shard collectives (see
        ``mesh_min_rows``) — so the single-device dispatch stays untouched
        and a sharded plane is never slower than an unsharded one on small
        fleets."""
        if self.plane is None or self.plane.mesh is None or nrows < self.mesh_min_rows:
            return {}
        return {
            "mesh": self.plane.mesh,
            "axis": self.plane.row_axis,
            "dim_axis": self.plane.dim_axis,
        }

    def _new_cluster(self, center: PyTree) -> Cluster:
        """``center`` may be a pytree or (plane mode) an already-flat row."""
        if self.backend == "plane":
            self._ensure_plane(center)
            row = self.plane.alloc(center)
            bcast_row = self.plane.alloc()
            self.plane.copy_row(row, bcast_row)
            c = Cluster(
                cluster_id=self._next_id, plane=self.plane, row=row, bcast_row=bcast_row
            )
        else:
            c = Cluster(cluster_id=self._next_id, center=center)
            c.last_broadcast_center = center
        c.ensure_snapshot_ring(self.snapshot_ring)
        self.clusters[self._next_id] = c
        self._next_id += 1
        return c

    def restore_cluster(self, cid: int, center: PyTree, bcast_center: PyTree) -> Cluster:
        """Rebuild one cluster from checkpointed pytrees (elastic restart)."""
        if self.backend == "plane":
            self._ensure_plane(center)
            row = self.plane.alloc(center)
            bcast_row = self.plane.alloc(bcast_center)
            c = Cluster(cluster_id=cid, plane=self.plane, row=row, bcast_row=bcast_row)
        else:
            c = Cluster(cluster_id=cid, center=center)
            c.last_broadcast_center = bcast_center
        c.ensure_snapshot_ring(self.snapshot_ring)
        self.clusters[cid] = c
        return c

    def drop_cluster(self, cid: int) -> None:
        self.clusters.pop(cid).release()

    def reset(self) -> None:
        """Drop every cluster (and return its plane rows) before a restore."""
        for c in self.clusters.values():
            c.release()
        self.clusters = {}

    # -------------------------------------------------------------- assign
    def upload_vec(self, update: PyTree):
        """Flat view of ``update`` (plane mode), reusing the assign-time
        flatten when this is the same object ``assign`` just processed."""
        p = self._pending
        if p is not None and p[0] is update:
            return p[2]
        self._ensure_plane(update)
        u = self.plane.from_pytree(update)
        self._pending = (update, None, u, None, -1)
        return u

    def assign(self, client_id, update: PyTree, switch_margin: float = 0.1) -> tuple[int, bool]:
        """On-arrival assignment (Eq. 1). Returns (cluster_id, is_new_cluster).

        ``switch_margin`` adds hysteresis: a client only leaves its current
        cluster when another center is at least that much (relatively) closer.
        Without it, aggregated centers drift toward the global parameter mean
        and sweep every client into one blob (centroid attraction) — the
        paper's refinement loop then thrashes expand/merge to undo it.
        """
        prev = self.assignment.get(client_id)
        if prev is not None and client_id in self.clusters[prev].partial_finetune:
            return prev, False  # expansion members stay put until next merge
        if self.backend == "plane":
            return self._assign_plane(client_id, update, switch_margin, prev)
        if len(self.clusters) < self.num_initial:
            c = self._new_cluster(update)
            self._move(client_id, c.cluster_id)
            return c.cluster_id, True
        cids = sorted(self.clusters)
        u = tree_flat_vector(update)
        centers = jnp.stack([tree_flat_vector(self.clusters[c].center) for c in cids])
        dists = fetch(K.l1_distance(u, centers), "assign")
        cid = cids[int(np.argmin(dists))]
        if prev is not None and prev in self.clusters and prev != cid:
            d_prev = dists[cids.index(prev)]
            if dists[cids.index(cid)] > (1.0 - switch_margin) * d_prev:
                cid = prev  # not decisively closer: stay
        self._move(client_id, cid)
        return cid, False

    def _assign_plane(self, client_id, update, switch_margin, prev) -> tuple[int, bool]:
        """Plane hot path: one flatten, one row gather, one fused kernel.

        ``assign_and_lerp`` returns the distances, the argmin, *and* the
        mixed-rate blend against the winning center — if the upcoming
        ``aggregate`` targets that same cluster (the common case), the
        center update is already computed and is written back as a single
        staged row."""
        self._ensure_plane(update)
        u = self.plane.from_pytree(update)
        if len(self.clusters) < self.num_initial:
            self._pending = (update, None, u, None, -1)
            c = self._new_cluster(u)
            self._move(client_id, c.cluster_id)
            return c.cluster_id, True
        cids = sorted(self.clusters)
        kw = self._kernel_mesh_kwargs(len(cids))
        centers = self.plane.rows([self.clusters[c]._row for c in cids], on_mesh=bool(kw))
        dists_d, _amin, blended = K.assign_and_lerp(u, centers, self.mix_rate, **kw)
        dists = fetch(dists_d, "assign")  # one host sync; argmin re-read from it
        cid = cids[int(np.argmin(dists))]
        # the blend is only valid against the center version it was computed
        # from; aggregate() re-checks under the branch write lock
        self._pending = (update, cid, u, blended, self.clusters[cid].version)
        if prev is not None and prev in self.clusters and prev != cid:
            d_prev = dists[cids.index(prev)]
            if dists[cids.index(cid)] > (1.0 - switch_margin) * d_prev:
                cid = prev  # not decisively closer: stay
        self._move(client_id, cid)
        return cid, False

    def _move(self, client_id, cid: int) -> None:
        prev = self.assignment.get(client_id)
        if prev is not None and prev in self.clusters:
            self.clusters[prev].members.discard(client_id)
            self.clusters[prev].partial_finetune.discard(client_id)
        self.clusters[cid].members.add(client_id)
        self.assignment[client_id] = cid

    # ----------------------------------------------------------- aggregate
    def aggregate(self, cid: int, update: PyTree, weight: float | None = None) -> None:
        """Asynchronous in-cluster aggregation: v_c <- (1-b) v_c + b u.

        EchoPFL deliberately does NOT decay b by staleness — slow devices'
        knowledge is preserved (Challenge #2); broadcast handles staleness.
        """
        c = self.clusters[cid]
        b = self.mix_rate if weight is None else weight
        if self.backend == "plane":
            p = self._pending
            # the fused blend only applies if the center is still at the
            # version assign saw — a concurrent push (this method runs under
            # the branch write lock) or an intervening merge falls back to a
            # live lerp so no aggregation is ever overwritten
            if (
                p is not None and p[0] is update and p[1] == cid
                and weight is None and c.version == p[4]
            ):
                c.set_center_vec(p[3])  # fused assign+lerp result: free update
            else:
                u = p[2] if p is not None and p[0] is update else self.upload_vec(update)
                self.plane.lerp_row(c._row, u, b)
                c._center_cache = None
            self._pending = None
        else:
            c.center = tree_lerp(c.center, update, b)
        c.version += 1

    # -------------------------------------------------------------- merging
    def should_merge(self) -> bool:
        # hm * C is the *maximized* cluster count (Sec. 7.4.4): merge only
        # when it is exceeded, so the system can stably hold hm*C clusters.
        return len(self.clusters) > self.hm * self.num_initial

    def merge_pair(
        self,
        cid_a: int,
        cid_b: int,
        local_train_fn: Callable[[PyTree], PyTree],
    ) -> int:
        """Algorithm 1: attention-weighted, training-free merge. The larger
        cluster's center is the main model; ``local_train_fn`` performs the
        one local training pass that yields the posterior direction."""
        a, b = self.clusters[cid_a], self.clusters[cid_b]
        main, aux = (a, b) if a.size >= b.size else (b, a)
        if self.backend == "plane":
            v_m = self.plane.row(main._row)
            v_aux = self.plane.row(aux._row)
            v_trained = self.plane.from_pytree(local_train_fn(main.center))
            main.set_center_vec(K.merge_attention(v_m, v_aux, v_trained))
        else:
            v_m = tree_flat_vector(main.center)
            v_aux = tree_flat_vector(aux.center)
            v_trained = tree_flat_vector(local_train_fn(main.center))
            merged_vec = K.merge_attention(v_m, v_aux, v_trained)
            main.center = tree_unflatten_vector(merged_vec, main.center)

        main.version += 1
        for client in list(aux.members):
            self._move(client, main.cluster_id)
        main.partial_finetune.clear()  # merge lifts the partial-finetune restriction
        self.drop_cluster(aux.cluster_id)
        self.merges += 1
        return main.cluster_id

    def nearest_pair(self, min_version: int = 2, close_frac: float | None = 0.5) -> tuple[int, int] | None:
        """Closest pair of centers by L1 — the merge candidates.

        Freshly-expanded clusters (version < min_version) are exempt while
        any mature pair exists: an expansion child starts at L1 = 0 from its
        parent and would otherwise be merged back before differentiating.

        A pair only qualifies when its distance is below ``close_frac`` of
        the median inter-center distance: merging is for *redundant*
        clusters, and folding two genuinely distinct centers just because
        capacity was reached re-creates the blob that expansion undid."""
        cids = sorted(self.clusters)
        mature = [c for c in cids if self.clusters[c].version >= min_version]
        if len(mature) >= 2:
            cids = mature
        if len(cids) < 2:
            return None
        if self.backend == "plane":
            kw = self._kernel_mesh_kwargs(len(cids))
            vecs = self.plane.rows([self.clusters[c]._row for c in cids], on_mesh=bool(kw))
            dmat = fetch(K.l1_distance_pairwise(vecs, vecs, **kw), "pairwise_l1")
        else:
            vecs = jnp.stack([tree_flat_vector(self.clusters[c].center) for c in cids])
            dmat = np.zeros((len(cids), len(cids)))
            for i in range(len(cids)):
                dmat[i] = fetch(K.l1_distance(vecs[i], vecs), "pairwise_l1")
        off = dmat[~np.eye(len(cids), dtype=bool)]
        median = float(np.median(off))
        dmat = dmat.copy()
        np.fill_diagonal(dmat, np.inf)
        i, j = np.unravel_index(np.argmin(dmat), dmat.shape)
        if close_frac is not None and len(cids) > 2 and dmat[i, j] > close_frac * median:
            return None  # nothing redundant enough to fold
        return (cids[i], cids[j])

    # ------------------------------------------------------- reassignment
    def reassign_poor_fits(
        self, feedbacks: dict[int, dict[Any, float]], uploads: dict[Any, Any]
    ) -> int:
        """Feedback-corrective reassignment: a member whose feedback is poor
        may simply belong to *another existing* cluster (initial assignment
        is fast but errorful — Sec. 4.2.2). Before spawning new clusters,
        move such members to a decisively closer center, bypassing the
        assignment hysteresis. Returns the number of moves.

        ``uploads`` maps client -> last upload: pytrees in pytree mode,
        plane row indices in plane mode (where all flagged members probe
        every center in a single pairwise launch).
        """
        if len(self.clusters) < 2:
            return 0
        cids = sorted(self.clusters)
        flagged: list[tuple[Any, int]] = []
        for cid, fb in feedbacks.items():
            if cid not in self.clusters or len(fb) < 2:
                continue
            med = float(np.median(list(fb.values())))
            for m, g in fb.items():
                if g <= 2.0 * (med + 1e-12) or m not in uploads:
                    continue
                if m in self.clusters[cid].partial_finetune:
                    continue
                flagged.append((m, cid))
        if not flagged:
            return 0
        moves = 0
        if self.backend == "plane":
            kw = self._kernel_mesh_kwargs(len(flagged))
            U = self._upload_matrix(uploads, [m for m, _ in flagged], on_mesh="shard" if kw else False)
            centers = self.plane.rows(
                [self.clusters[c]._row for c in cids], on_mesh=bool(kw)
            )
            D = fetch(K.l1_distance_pairwise(U, centers, **kw), "pairwise_l1")
            for (m, cid), d in zip(flagged, D):
                best = cids[int(np.argmin(d))]
                if best != cid and d[cids.index(best)] < 0.9 * d[cids.index(cid)]:
                    self._move(m, best)
                    moves += 1
            return moves
        centers = jnp.stack([tree_flat_vector(self.clusters[c].center) for c in cids])
        for m, cid in flagged:
            u = tree_flat_vector(uploads[m])
            d = fetch(K.l1_distance(u, centers), "pairwise_l1")
            best = cids[int(np.argmin(d))]
            if best != cid and d[cids.index(best)] < 0.9 * d[cids.index(cid)]:
                self._move(m, best)
                moves += 1
        return moves

    # ------------------------------------------------------------ expansion
    def expand(
        self,
        cid: int,
        feedbacks: dict[Any, float],
        frac: float = 0.2,
        uploads: dict[Any, Any] | None = None,
        refine_round: int = 0,
    ) -> int | None:
        """Sec. 4.3.3: clients whose feedback ranks in the worst ``frac`` of
        their cluster split into a new cluster and enter head-only
        fine-tuning mode until the next merging refinement.

        The child center realizes the paper's "transfer learning upon the
        original cluster": it starts from the mean of the peeled members'
        own uploads — which *are* the original center fine-tuned on the
        drifted local data — so the new cluster is immediately separable
        from its parent instead of being reabsorbed at the next merge.

        ``uploads`` holds pytrees in pytree mode, plane rows in plane mode.
        """
        c = self.clusters[cid]
        if self._last_expand_round.get(cid, -10) >= refine_round - 1:
            return None  # cooldown: let the last split differentiate first
        members = [m for m in c.members if m in feedbacks]
        if len(members) < 3:
            return None
        ranked = sorted(members, key=lambda m: feedbacks[m])  # ascending: low = good fit
        n_bad = max(1, int(len(ranked) * frac))
        median = feedbacks[ranked[len(ranked) // 2]]
        worst = feedbacks[ranked[-1]]
        if worst <= 1e-9 or worst < 2.0 * (median + 1e-12):
            return None  # cluster fits its members uniformly — nothing to split
        # peel the worst-20%, but only members that are individually poor
        # fits and not serial peel victims (inherent outliers stay put)
        bad = [
            m for m in ranked[-n_bad:]
            if feedbacks[m] > 1.5 * (median + 1e-12) and self.peel_counts.get(m, 0) < 3
        ]
        if not bad:
            return None
        if self.backend == "plane":
            have = [m for m in bad if uploads and m in uploads]
            if have:
                vecs = self._upload_matrix(uploads, have)
                seed_center = vecs[0]
                for i in range(1, len(have)):  # same running mean as pytree path
                    t = 1.0 / (i + 1)
                    seed_center = (1.0 - t) * seed_center + t * vecs[i]
            else:
                seed_center = self.plane.row(c._row)
        else:
            seeds = [uploads[m] for m in bad if uploads and m in uploads]
            if seeds:
                seed_center = seeds[0]
                for i, s in enumerate(seeds[1:], start=2):
                    seed_center = tree_lerp(seed_center, s, 1.0 / i)  # running mean
            else:
                seed_center = c.center
        new = self._new_cluster(seed_center)
        for client in bad:
            self._move(client, new.cluster_id)
            new.partial_finetune.add(client)
            self.peel_counts[client] = self.peel_counts.get(client, 0) + 1
        new.pf_round = refine_round
        self._last_expand_round[cid] = refine_round
        self._last_expand_round[new.cluster_id] = refine_round
        self.expansions += 1
        return new.cluster_id

    # ------------------------------------------------------------- helpers
    def _upload_matrix(self, uploads: dict, keys: list, on_mesh: bool | str = False) -> Any:
        """Stack clients' last uploads into (len(keys), dim). Values may be
        plane row indices (the server's plane-mode store), flat vectors, or
        pytrees (direct API use / tests) — rows take the one-gather path.
        ``on_mesh="shard"`` serves a fleet-scale sweep (reassign/dissolve
        over many upload rows) sharded over the plane mesh's row axis, so a
        mesh-committed plane never funnels the batch through one device."""
        vals = [uploads[m] for m in keys]
        if vals and all(isinstance(v, (int, np.integer)) for v in vals):
            # one-shot row set (flagged members change every sweep): the
            # uncached gather, so the hot cached views survive refinement
            return self.plane.take(vals, on_mesh=on_mesh)
        return jnp.stack([self.plane.as_vec(v) for v in vals])

    def membership_matrix(self, client_ids: list) -> np.ndarray:
        """Boolean collaboration matrix (Fig. 11): M[i, j] = same cluster."""
        n = len(client_ids)
        out = np.zeros((n, n), bool)
        for i, a in enumerate(client_ids):
            for j, b in enumerate(client_ids):
                out[i, j] = (
                    self.assignment.get(a) is not None
                    and self.assignment.get(a) == self.assignment.get(b)
                )
        return out
