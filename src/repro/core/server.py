"""The EchoPFL server: asynchronous PFL coordination with on-demand
broadcast (the paper's core contribution, Secs. 3-6 wired together).

Per arriving update:
  1. assign/confirm cluster (on-arrival L1 clustering, Eq. 1),
  2. record staleness (never decay/drop — Challenge #2),
  3. aggregate into the cluster branch (CI push, RW-locked),
  4. update the cluster's Top-K change records and online fine-tune the
     predictor on the realized ground truth (Eq. 4),
  5. unicast the fresh center back to the uploader (prompt CI feedback),
  6. RNN broadcast decision: maybe broadcast to the *other* in-cluster
     members (the "echo" — rides the fat downstream link),
  7. periodically: feedback-aware refinement (expand bad fits, merge when
     cluster count reaches hm x C via Algorithm 1).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tracing
from repro.common.pytrees import tree_flat_vector, tree_l1
from repro.common.tracing import fetch, span
from repro.core.broadcast import (
    BroadcastPredictor,
    build_seq,
    predictor_batch_enabled,
    predictor_for_expansion,
    predictor_for_merge,
    pretrain_rnn,
)
from repro.core.clustering import DynamicClustering
from repro.core.plane import l1_vec
from repro.core.staleness import StalenessTracker
from repro.core.versioning import ModelRepo
from repro.kernels import ops as K

PyTree = Any


@dataclasses.dataclass
class _PredictorPlan:
    """Resolved predictor work for one refinement sub-window: per-step
    broadcast outcomes and the chain launch's final RNN weights, written
    back at window end (before any refine can inherit them)."""

    wants: dict  # step index -> planned decide() outcome
    new_params: dict  # cid -> batched-chain final RNN params (device)
    launches: int  # fused chain launches the plan made


@dataclasses.dataclass
class Downlink:
    client_id: Any
    params: PyTree
    version: int
    cluster_id: int
    reason: str  # "unicast" | "broadcast"


class EchoPFLServer:
    name = "echopfl"
    is_synchronous = False

    def __init__(
        self,
        init_params: PyTree,
        *,
        num_initial_clusters: int = 2,
        mix_rate: float = 0.25,
        hm: float = 2.0,
        top_k: int = 10,
        refine_every: int = 20,
        feedback_fn: Callable[[Any, PyTree], tuple[np.ndarray, np.ndarray, np.ndarray]] | None = None,
        local_train_fn: Callable[[PyTree], PyTree] | None = None,
        pretrain_key: jax.Array | None = None,
        enable_clustering: bool = True,
        enable_broadcast: bool = True,
        plane_backend: str | None = None,
        plane_mesh: Any | None = None,
        seed: int = 0,
    ):
        self.init_params = init_params
        self.clustering = DynamicClustering(
            num_initial_clusters,
            mix_rate=mix_rate,
            hm=hm,
            backend=plane_backend,
            mesh=plane_mesh,
        )
        self.repo = ModelRepo()
        self.staleness = StalenessTracker()
        self.top_k = top_k
        self.refine_every = refine_every
        self.feedback_fn = feedback_fn
        # optional batched probe: called with [(member, center), ...] and
        # returns pre-stacked (F_pred, F_true, S_soft) — one launch for the
        # whole pair list. The simulator's fleet engine installs its
        # ``feedback_many`` here; when unset, pairs probe via feedback_fn.
        self.feedback_batch_fn: Callable[[list], tuple] | None = None
        # optional uplink codec (REPRO_UPLINK): attached by the simulator so
        # the per-client anchor/residual rows ride this server's checkpoints
        self.uplink_codec = None
        self._pending_uplink_state: tuple | None = None
        # optional ingest guard (REPRO_GUARD): attached by the simulator.
        # None (the default) keeps every guard hook inert — the ingest
        # launches compile without stats and no snapshot rings allocate.
        self.guard = None
        self.local_train_fn = local_train_fn
        self.enable_clustering = enable_clustering
        self.enable_broadcast = enable_broadcast
        self._uploads = 0
        self._decisions = 0  # cumulative (predictor objects are replaced on refine)
        self._rnn_broadcasts = 0
        self._refine_round = 0
        self.last_uploads: dict[Any, PyTree] = {}  # pytree mode: client -> last update
        self._upload_rows: dict[Any, int] = {}  # plane mode: client -> plane row
        self.last_cluster_feedback_mean: dict[int, float] = {}
        self._rng = np.random.default_rng(seed)
        key = pretrain_key if pretrain_key is not None else jax.random.PRNGKey(seed)
        self._rnn_init = pretrain_rnn(key) if enable_broadcast else None
        self.predictors: dict[int, BroadcastPredictor] = {}
        self.client_versions: dict[Any, tuple[int, int]] = {}  # cid -> (cluster, version)
        self.events: list[dict] = []

    # ------------------------------------------------------------ protocol
    def initial_models(self, client_ids: list) -> dict[Any, PyTree]:
        return {cid: self.init_params for cid in client_ids}

    def model_for(self, client_id) -> PyTree:
        cid = self.clustering.assignment.get(client_id)
        if cid is None:
            return self.init_params
        return self.clustering.clusters[cid].center

    def attach_uplink_codec(self, codec) -> None:
        """Adopt the simulator's uplink codec: its anchors/residuals become
        part of :meth:`state_dict`/:meth:`load_state`. A restore that ran
        BEFORE the codec existed (load_state then start the run) stashed the
        codec section; it is replayed into the codec here."""
        self.uplink_codec = codec
        if codec is not None and self._pending_uplink_state is not None:
            codec.load_state(*self._pending_uplink_state)
            self._pending_uplink_state = None

    def attach_guard(self, guard) -> None:
        """Adopt the simulator's ingest guard
        (:class:`~repro.fl.guard.IngestGuard`): enables the post-blend
        center-norm check (late poison detection) and equips every
        cluster — present and future — with a last-known-good snapshot
        ring so a detection can roll the center back and re-broadcast.
        The retrofit loop covers clusters restored from a checkpoint
        before the guard attached (kill + restore under chaos)."""
        self.guard = guard
        if guard is None:
            return
        self.clustering.snapshot_ring = guard.cfg.snapshot_ring
        for c in self.clustering.clusters.values():
            c.ensure_snapshot_ring(guard.cfg.snapshot_ring)

    def _predictor(self, cluster_id: int) -> BroadcastPredictor:
        if cluster_id not in self.predictors:
            size = self.clustering.clusters[cluster_id].size
            self.predictors[cluster_id] = BroadcastPredictor(
                params=self._rnn_init, k=max(self.top_k, size)
            )
        return self.predictors[cluster_id]

    def handle_upload(
        self, client_id, params: PyTree, base_version: int, n_samples: int, t: float
    ) -> list[Downlink]:
        self._uploads += 1
        out: list[Downlink] = []

        # 1. cluster assignment (or the single global "cluster" in ablation)
        if self.enable_clustering:
            cid, created = self.clustering.assign(client_id, params)
        else:
            if not self.clustering.clusters:
                self.clustering._new_cluster(self.init_params)
            cid, created = 0, False
            self.clustering._move(client_id, 0)
        cluster = self.clustering.clusters[cid]
        plane = self.clustering.plane
        if plane is None:
            self.last_uploads[client_id] = params
        else:
            # plane mode: the last upload lives in a plane row (staged write;
            # flushed in one scatter at the next batched read), reusing the
            # flatten `assign` already did for this same object
            row = self._upload_rows.get(client_id)
            if row is None:
                row = self._upload_rows[client_id] = plane.alloc()
            plane.write(row, self.clustering.upload_vec(params))
        # the branch head is only materialized on branch creation; in plane
        # mode it tracks the flat row (the protocol never pulls it back)
        try:
            branch = self.repo.branch(f"cluster/{cid}")
        except KeyError:
            branch = self.repo.branch(
                f"cluster/{cid}", cluster.center if plane is None else cluster.center_vec
            )

        # 2. staleness bookkeeping (all updates included, none dropped)
        base_cluster, base_ver = self.client_versions.get(client_id, (cid, 0))
        if base_cluster == cid:
            staleness = max(0, cluster.version - base_ver)
        elif base_cluster in self.clustering.clusters:
            # reassigned client: staleness is measured against the branch it
            # actually trained from, not the whole history of the new branch
            staleness = max(0, self.clustering.clusters[base_cluster].version - base_ver)
        else:
            # base branch was merged away; the merge broadcast refreshed
            # every member, so only post-broadcast aggregations are stale
            staleness = max(0, cluster.version - cluster.last_broadcast_version)
        self.staleness.record(staleness)

        # 3. aggregate = CI push into the branch
        pred = self._predictor(cid) if self.enable_broadcast else None
        if pred is not None:  # the pre-update center only feeds the predictor
            prev_center = cluster.center if plane is None else cluster.center_vec

        def merge_fn(head):
            self.clustering.aggregate(cid, params)
            c = self.clustering.clusters[cid]
            return c.center if plane is None else c.center_vec
        branch.push(merge_fn)

        # 3b. late poison detection (guard only): a non-finite or
        # MAD-blown post-blend center norm vetoes the blend — roll back
        # to the last-known-good snapshot and re-broadcast. The corrupt
        # blend never feeds the predictor, and the uploader learns the
        # restored center through the recovery broadcast.
        if self.guard is not None and not self.guard.center_ok(
            cid, self._center_norm(cluster)
        ):
            out.extend(self._rollback_center(cluster, branch, client_id))
            if self._uploads % self.refine_every == 0:
                out.extend(self._refine())
            return out

        # 4. Top-K change record + online fine-tune on the ground-truth
        #    label for the previous decision (Eq. 4)
        if pred is not None:
            if plane is None:
                change = float(fetch(tree_l1(cluster.center, prev_center), "gap"))
            else:
                change = float(fetch(l1_vec(cluster.center_vec, prev_center), "gap"))
            if plane is None:
                gap_before = float(fetch(tree_l1(prev_center, cluster.last_broadcast_center), "gap"))
            else:
                gap_before = float(fetch(l1_vec(prev_center, cluster.broadcast_vec), "gap"))
            # Ground truth for the decision made before this upload (Eq. 4,
            # with the sign read per the Sec. 5.2.1 text rule): the realized
            # model change exceeding the accumulated gap since the last
            # broadcast means the broadcast was warranted.
            label = 1 if change > gap_before else 0
            if pred.records:
                pred.learn(label)
            pred.observe(change)

        # 5. unicast fresh center to the uploader
        out.append(Downlink(client_id, cluster.center, cluster.version, cid, "unicast"))
        self.client_versions[client_id] = (cid, cluster.version)

        # 6. on-demand broadcast to the rest of the cluster
        if pred is not None and cluster.size > 1:
            if plane is None:
                gap = float(fetch(tree_l1(cluster.center, cluster.last_broadcast_center), "gap"))
            else:
                gap = float(fetch(l1_vec(cluster.center_vec, cluster.broadcast_vec), "gap"))
            self._decisions += 1
            if pred.decide(gap):
                self._rnn_broadcasts += 1
                out.extend(self._broadcast(cluster, exclude={client_id}))

        # 7. periodic refinement
        if self._uploads % self.refine_every == 0:
            out.extend(self._refine())
        return out

    # ------------------------------------------------------- batched ingest
    def handle_uploads(self, batch: list[tuple]) -> list[list[Downlink]]:
        """Batched ingest of concurrently-arrived uploads (the event-coalesced
        async path): ``batch`` is a list of ``handle_upload`` argument tuples
        ``(client_id, params, base_version, n_samples, t)`` in event order.
        Returns one downlink list per upload, exactly what N sequential
        ``handle_upload`` calls would return.

        Uploads are processed in *segments* of consecutive distinct clients:
        each segment's cluster assignment + mixed-rate blends run as ONE
        fused scan launch (``kernels.ops.ingest_chain`` —
        sequential-equivalent: step j scores against the centers already
        blended by steps < j), and the host replays only the per-upload
        protocol bookkeeping (staleness, CI branch pushes, predictor
        bookkeeping, downlink construction) from the precomputed
        statistics. Predictor learn/decide work is itself batched into one
        fused RNN chain launch per refinement sub-window
        (``REPRO_PREDICTOR_BATCH``; see :meth:`_plan_predictor_window`).

        Refinement no longer cuts segments: the chain launch speculatively
        spans refine boundaries, and after each mid-segment refine the
        replay revalidates the launch's assumptions (cluster set unchanged,
        per-upload prev/forced indices still correct). A refine that moved
        clients, lifted partial-finetune pins, or changed the cluster set
        invalidates the remainder, which simply relaunches from live state.
        Remaining segment boundaries — a repeated client, the seeding
        phase, the pytree backend — fall back to the per-upload path, so
        trajectories are identical to the unbatched loop by construction."""
        out: list[list[Downlink]] = []
        i, n = 0, len(batch)
        while i < n:
            cl = self.clustering
            if (
                cl.plane is None
                or not self.enable_clustering
                or len(cl.clusters) < cl.num_initial
            ):
                with span("ingest/single", reason="seeding"):
                    out.append(self.handle_upload(*batch[i]))
                i += 1
                continue
            # segment: consecutive distinct clients
            seen: set = set()
            j = i
            while j < n and batch[j][0] not in seen:
                seen.add(batch[j][0])
                j += 1
            if j - i < 2:
                with span("ingest/single", reason="repeat"):
                    out.append(self.handle_upload(*batch[i]))
                i += 1
                continue
            seg_out, consumed = self._handle_upload_segment(batch[i:j])
            out.extend(seg_out)
            i += consumed
        return out

    def _handle_upload_segment(self, seg: list[tuple]) -> tuple[list[list[Downlink]], int]:
        """One fused-launch segment of :meth:`handle_uploads` (plane mode).

        Returns ``(downlink lists, uploads consumed)``: a mid-segment
        refinement that invalidates the speculative launch (moved clients,
        lifted pins, changed cluster set) stops the replay right after the
        refine; the caller relaunches the remainder from live state."""
        cl = self.clustering
        plane = cl.plane
        cid_order = sorted(cl.clusters)
        pos = {c: k for k, c in enumerate(cid_order)}
        S = len(seg)

        with span("ingest/chain", steps=S, centers=len(cid_order)) as chain:
            # one flatten per upload, one stacked write into the upload rows
            # (the same vectors the per-event path writes one at a time)
            U = jnp.stack([plane.from_pytree(item[1]) for item in seg])
            upload_rows = []
            for item in seg:
                row = self._upload_rows.get(item[0])
                if row is None:
                    row = self._upload_rows[item[0]] = plane.alloc()
                upload_rows.append(row)
            plane.write_rows(upload_rows, U)

            prev_idx, forced_idx = [], []
            for item in seg:
                prev = cl.assignment.get(item[0])
                alive = prev is not None and prev in cl.clusters
                pf = alive and item[0] in cl.clusters[prev].partial_finetune
                prev_idx.append(pos[prev] if alive else -1)
                forced_idx.append(pos[prev] if pf else -1)

            P = 1 << (S - 1).bit_length()  # pad the scan length: O(log window) jit cache
            valid = [True] * S + [False] * (P - S)
            if P != S:
                U = jnp.concatenate([U, jnp.broadcast_to(U[:1], (P - S, U.shape[1]))])
                prev_idx += [-1] * (P - S)
                forced_idx += [-1] * (P - S)

            C0 = plane.rows([cl.clusters[c]._row for c in cid_order])
            B0 = plane.rows([cl.clusters[c]._bcast_row for c in cid_order])
            Cn = len(cid_order)
            Cp = 1 << (Cn - 1).bit_length()  # pow2-padded: O(log clusters) jit cache
            if Cp != Cn:
                zpad = jnp.zeros((Cp - Cn, C0.shape[1]), C0.dtype)
                C0 = jnp.concatenate([C0, zpad])
                B0 = jnp.concatenate([B0, zpad])
            guard = self.guard
            res = K.ingest_chain(
                U, C0, B0, prev_idx, forced_idx, valid,
                beta=cl.mix_rate, num_centers=Cn, with_stats=guard is not None,
            )
            chain.set_metadata(padded=P - S, padded_centers=Cp - Cn)
        # ONE host sync for the whole segment (stats + blended rows: the
        # per-upload center writes re-enter the plane as staged host rows).
        # The guard's post-blend center norms ride the same launch and sync.
        if guard is not None:
            cids_d, blended_d, change_d, gb_d, ga_d, cn_d = res
            cids_np, change_np, gb_np, ga_np, cnorm_np, blended = fetch(
                (cids_d[:S], change_d[:S], gb_d[:S], ga_d[:S], cn_d[:S], blended_d[:S]), "chain"
            )
        else:
            cids_d, blended_d, change_d, gb_d, ga_d = res
            cids_np, change_np, gb_np, ga_np, blended = fetch(
                (cids_d[:S], change_d[:S], gb_d[:S], ga_d[:S], blended_d[:S]), "chain"
            )
            cnorm_np = None
        blended = np.asarray(blended)
        blended.flags.writeable = False  # unicast payloads are views of this

        step_cids = [cid_order[int(cids_np[j])] for j in range(S)]
        out: list[list[Downlink]] = []
        last_vec: dict[int, Any] = {}  # cid -> live center row (host, np)
        bcast_np: dict[int, Any] = {}  # cid -> anchor moved mid-segment (np)
        batch_pred = self.enable_broadcast and predictor_batch_enabled()
        j0 = 0
        while j0 < S:
            # predictor sub-window: up to and including the next refine
            # boundary — a refine's predictor maintenance (expansion/merge
            # inheritance) must see RNN weights as of refine time, so the
            # fused chain launch never crosses it
            until_refine = self.refine_every - (self._uploads % self.refine_every)
            j1 = min(S, j0 + until_refine)
            # guard pre-walk: consume the fused launch's post-blend center
            # norms in step order BEFORE planning predictor work — on a
            # clean window this records exactly what per-step checks would
            # (all-accept, plan untouched); a detection at step f voids the
            # speculative launch from f on, so the window falls back to the
            # serial predictor path and the replay aborts right after f
            guard_fail = None
            if cnorm_np is not None:
                for jj in range(j0, j1):
                    if not guard.center_ok(step_cids[jj], float(cnorm_np[jj])):
                        guard_fail = jj
                        break
            plan = None
            if batch_pred and guard_fail is None:
                with span("ingest/predictor") as sp:
                    plan = self._plan_predictor_window(
                        seg, j0, j1, step_cids, forced_idx,
                        change_np, gb_np, ga_np, blended, bcast_np, last_vec,
                    )
                    if tracing.on():
                        sp.set_metadata(clusters=len(set(step_cids[j0:j1])), launches=plan.launches)
            with span("ingest/replay"):
                for j in range(j0, j1):
                    client_id, params, base_version, n_samples, t = seg[j]
                    self._uploads += 1
                    msgs: list[Downlink] = []
                    cid = step_cids[j]
                    cluster = cl.clusters[cid]
                    if forced_idx[j] < 0:  # partial-finetune members stay put, no move
                        cl._move(client_id, cid)
                    try:
                        branch = self.repo.branch(f"cluster/{cid}")
                    except KeyError:
                        branch = self.repo.branch(f"cluster/{cid}", cluster.center_vec)

                    # staleness bookkeeping — identical to handle_upload
                    base_cluster, base_ver = self.client_versions.get(client_id, (cid, 0))
                    if base_cluster == cid:
                        staleness = max(0, cluster.version - base_ver)
                    elif base_cluster in cl.clusters:
                        staleness = max(0, cl.clusters[base_cluster].version - base_ver)
                    else:
                        staleness = max(0, cluster.version - cluster.last_broadcast_version)
                    self.staleness.record(staleness)

                    pred = self._predictor(cid) if self.enable_broadcast else None
                    new_vec = blended[j]

                    def merge_fn(head, cluster=cluster, vec=new_vec):
                        cluster.set_center_vec(vec)
                        cluster.version += 1
                        return cluster.center_vec

                    branch.push(merge_fn)

                    if j == guard_fail:
                        # the carried center matrix is corrupt from this step
                        # on: roll back, hand the remainder back for a relaunch
                        # from the restored live state (same abort discipline as
                        # a refine that invalidates the speculative launch)
                        msgs.extend(self._rollback_center(cluster, branch, client_id))
                        if self._uploads % self.refine_every == 0:
                            msgs.extend(self._refine())
                        out.append(msgs)
                        cl._pending = None
                        return out, j + 1

                    if pred is not None:
                        change = float(change_np[j])
                        if plan is None:
                            b_moved = bcast_np.get(cid)
                            if b_moved is not None:
                                # an intra-window broadcast moved this cluster's
                                # anchor: the precomputed gap is stale. The anchor
                                # AND the pre-blend center are both host rows we
                                # already hold (the broadcast step's blended row),
                                # so the recompute is pure numpy — no device
                                # round-trip per upload.
                                gap_before = float(np.abs(last_vec[cid] - b_moved).sum(dtype=np.float32))
                            else:
                                gap_before = float(gb_np[j])
                            label = 1 if change > gap_before else 0
                            if pred.records:
                                pred.learn(label)
                        # with a plan, the fused chain launch already applied the
                        # SGD steps on host-exact labels; only the record window
                        # bookkeeping happens per upload
                        pred.observe(change)

                    # unicast payload: host-side numpy views of the blended row we
                    # already synced — bitwise the center the per-event path would
                    # materialize, with zero device dispatches
                    msgs.append(
                        Downlink(client_id, plane.spec.unflatten_np(new_vec), cluster.version, cid, "unicast")
                    )
                    self.client_versions[client_id] = (cid, cluster.version)

                    if pred is not None and cluster.size > 1:
                        self._decisions += 1
                        if plan is None:
                            b_moved = bcast_np.get(cid)
                            if b_moved is not None:
                                gap = float(np.abs(new_vec - b_moved).sum(dtype=np.float32))
                            else:
                                gap = float(ga_np[j])
                            want = pred.decide(gap)
                        else:
                            # mirror BroadcastPredictor.decide with the planned
                            # outcome — counters and the one-suppressed-decision
                            # activation stay host-exact
                            pred.decisions += 1
                            if not pred.active:
                                pred.active = True
                                want = False
                            else:
                                want = plan.wants[j]
                            if want:
                                pred.broadcasts += 1
                        if want:
                            self._rnn_broadcasts += 1
                            msgs.extend(self._broadcast(cluster, exclude={client_id}))
                            bcast_np[cid] = new_vec  # snapshot_broadcast just copied it
                    last_vec[cid] = new_vec

                    if j == j1 - 1 and plan is not None:
                        # write the fused chain's final RNN weights back before a
                        # refine can inherit them (expansion/merge maintenance)
                        for wcid, wparams in plan.new_params.items():
                            self.predictors[wcid].params = wparams
                    if self._uploads % self.refine_every == 0:
                        msgs.extend(self._refine())
                        out.append(msgs)
                        if j + 1 < S and not self._segment_continuation_valid(
                            seg, j + 1, cid_order, prev_idx, forced_idx
                        ):
                            # the refine changed what the speculative launch
                            # assumed: hand the remainder back for a relaunch
                            cl._pending = None
                            return out, j + 1
                    else:
                        out.append(msgs)
            j0 = j1
        cl._pending = None  # the fused path never uses the assign-time cache
        return out, S

    def _segment_continuation_valid(
        self, seg: list[tuple], start: int, cid_order: list, prev_idx: list, forced_idx: list
    ) -> bool:
        """Did a mid-segment refine leave the speculative chain launch valid
        for the remaining uploads? The launch fixed (a) the cluster set and
        its center/anchor rows and (b) each upload's prev/forced index.
        Expansion, merge and dissolve all change the cluster set (and every
        center write rides on those), so (a) catches them; feedback
        reassignment and partial-finetune lifts change (b)."""
        cl = self.clustering
        if sorted(cl.clusters) != cid_order:
            return False
        pos = {c: k for k, c in enumerate(cid_order)}
        for j in range(start, len(seg)):
            client = seg[j][0]
            prev = cl.assignment.get(client)
            alive = prev is not None and prev in cl.clusters
            pf = alive and client in cl.clusters[prev].partial_finetune
            if prev_idx[j] != (pos[prev] if alive else -1):
                return False
            if forced_idx[j] != (pos[prev] if pf else -1):
                return False
        return True

    def _plan_predictor_window(
        self, seg, j0, j1, step_cids, forced_idx,
        change_np, gb_np, ga_np, blended, bcast_np, last_vec,
    ) -> "_PredictorPlan | None":
        """Plan one refinement sub-window's predictor work as one fused RNN
        chain launch per touched cluster (``kernels.ops.predictor_chain``).

        The serial path pays two jit dispatches plus a blocking want-sync
        per upload. All of that work is a deterministic function of state
        we already hold on the host: record windows evolve by the synced
        ``change`` stats alone, gates (learn: records nonempty; decide:
        cluster size > 1 with active/cold-start kinds) are
        decision-independent, and only the Eq. 4 *labels* and the
        cold-start fallback decisions depend on broadcast anchors that
        intra-window decisions may move. A structure pass replays
        membership + record evolution without touching live state, and
        the label/decision circularity is resolved IN-SCAN: within a
        window a cluster's anchor can only be its pre-window anchor or
        the blended vector of an earlier fired step of the same chain, so
        the planner precomputes each step's label (and each cold-start
        fallback decision) for every possible "last fired position" with
        exact host float64 arithmetic, and the chain's scan carries the
        fired position and gathers from those rows. Every step executes
        once; one decision sync per window covers all clusters.

        Inactive (post-expansion) decisions need no device work and are
        computed host-side, mirroring :meth:`BroadcastPredictor.decide`;
        the final host ``resolve`` replay under the synced RNN decisions
        recomputes fallback fires with the same float64 rules the tables
        were built from, keeping the returned bookkeeping host-exact.
        """
        cl = self.clustering

        # ---- structure pass: decision-independent step data -------------
        sim_size: dict[int, int] = {}
        sim_assign: dict[Any, int] = {}
        pstate: dict[int, dict] = {}  # cid -> simulated predictor state

        def size_of(c):
            return sim_size.get(c, cl.clusters[c].size)

        def pred_of(c):
            ps = pstate.get(c)
            if ps is None:
                live = self.predictors.get(c)
                if live is not None:
                    ps = {
                        "records": list(live.records), "scale": live.scale,
                        "active": live.active, "k": live.k, "params": live.params,
                    }
                else:  # _predictor() creates at first touch, k from live size
                    ps = {
                        "records": [], "scale": 1.0, "active": True,
                        "k": max(self.top_k, size_of(c)), "params": self._rnn_init,
                    }
                pstate[c] = ps
            return ps

        steps = []
        for j in range(j0, j1):
            client = seg[j][0]
            cid = step_cids[j]
            if forced_idx[j] < 0:  # mirror cl._move's size effects
                prev = sim_assign.get(client, cl.assignment.get(client))
                if prev != cid:
                    if prev is not None and prev in cl.clusters:
                        sim_size[prev] = size_of(prev) - 1
                    sim_size[cid] = size_of(cid) + 1
                sim_assign[client] = cid
            ps = pred_of(cid)
            change = float(change_np[j])
            learn_gate = len(ps["records"]) > 0
            seq_pre = build_seq(ps["records"], ps["k"]) if learn_gate else None
            # observe(), host-exact
            ps["records"].append(change)
            ps["records"] = ps["records"][-max(ps["k"], 1):]
            ps["scale"] = 0.9 * ps["scale"] + 0.1 * max(abs(change), 1e-12)
            kind, seq_post = "none", None
            if size_of(cid) > 1:
                if not ps["active"]:
                    kind = "inactive"
                    ps["active"] = True
                elif len(ps["records"]) < 2:
                    kind = "fallback"
                else:
                    kind = "rnn"
                    seq_post = build_seq(ps["records"], ps["k"])
            steps.append({
                "j": j, "cid": cid, "change": change, "learn": learn_gate,
                "seq_pre": seq_pre, "kind": kind, "seq_post": seq_post,
                "scale": ps["scale"],
            })

        # ---- label/decision resolution under a set of RNN outcomes ------
        def resolve(rnn_wants: dict) -> tuple[dict, dict]:
            anchors = dict(bcast_np)
            lastv = dict(last_vec)
            labels: dict[int, int] = {}
            wants: dict[int, bool] = {}
            for st in steps:
                j, cid = st["j"], st["cid"]
                a = anchors.get(cid)
                if a is not None:
                    gap_before = float(np.abs(lastv[cid] - a).sum(dtype=np.float32))
                else:
                    gap_before = float(gb_np[j])
                labels[j] = 1 if st["change"] > gap_before else 0
                want = False
                if st["kind"] == "fallback":
                    if a is not None:
                        gap = float(np.abs(blended[j] - a).sum(dtype=np.float32))
                    else:
                        gap = float(ga_np[j])
                    want = gap > 1.0 * st["scale"]  # decide()'s fallback rule
                elif st["kind"] == "rnn":
                    want = bool(rnn_wants.get(j, False))
                wants[j] = want
                if want:
                    anchors[cid] = blended[j]
                lastv[cid] = blended[j]
            return labels, wants

        # ---- fused launch: in-scan label/decision resolution ------------
        # A chain covers every step of a cluster that learns, decides via
        # the RNN, or decides via the cold-start fallback — the latter two
        # can fire a broadcast and move the anchor that later labels and
        # fallback gaps read. Within one window that anchor is either the
        # pre-window anchor or the blended vector of an earlier fired step
        # of the SAME chain, so every anchor-dependent comparison is
        # enumerable on the host: build, per step, a boolean row over
        # "last fired chain position" with the exact float64 expressions
        # resolve() uses, and let the scan carry the fired position and
        # gather from the rows (no float compare ever runs on device).
        # One launch per cluster, one decision sync per window, every step
        # executed exactly once — no fixpoint iteration, no relaunches.
        chains: dict[int, list] = {}
        for st in steps:
            if st["learn"] or st["kind"] in ("rnn", "fallback"):
                chains.setdefault(st["cid"], []).append(st)
        rnn_any = any(st["kind"] == "rnn" for st in steps)
        launch_cids = [
            c for c in sorted(chains)
            if any(st["learn"] or st["kind"] == "rnn" for st in chains[c])
        ]
        if not launch_cids:  # no device work at all this window
            _, wants = resolve({})
            return _PredictorPlan(wants=wants, new_params={}, launches=0)

        # last-upload vector seen by each step BEFORE it runs (evolves at
        # every step of its cluster, chain member or not — mirrors the
        # ``lastv`` updates in resolve())
        lastv_sim = dict(last_vec)
        lastv_before: dict[int, Any] = {}
        for st in steps:
            lastv_before[st["j"]] = lastv_sim.get(st["cid"])
            lastv_sim[st["cid"]] = blended[st["j"]]

        wants_dev: dict[int, Any] = {}
        finals: dict[int, Any] = {}
        for c in launch_cids:
            sub = chains[c]
            k = pred_of(c)["k"]
            # pow2-padded shapes keep the jit cache O(log window x log K);
            # per-cluster launches keep it independent of cluster count
            Kp = 1 << (k - 1).bit_length()
            Sp = 1 << (len(sub) - 1).bit_length()
            pre = np.zeros((Sp, Kp, 1), np.float32)
            post = np.zeros((Sp, Kp, 1), np.float32)
            lab_t = np.zeros((Sp, Sp + 1), np.int32)
            fb_t = np.zeros((Sp, Sp + 1), bool)
            lgate = np.zeros(Sp, bool)
            dgate = np.zeros(Sp, bool)
            fgate = np.zeros(Sp, bool)
            anchor0 = bcast_np.get(c)
            for p, st in enumerate(sub):
                j = st["j"]
                lv = lastv_before[j]
                # anchor candidates live when step p runs: column 0 = the
                # pre-window anchor, column q+1 = chain step q fired last
                cand = [(0, anchor0)] + [
                    (q + 1, blended[sub[q]["j"]]) for q in range(p)
                    if sub[q]["kind"] in ("rnn", "fallback")
                ]
                if st["learn"]:
                    pre[p, Kp - k:, :] = st["seq_pre"]
                    lgate[p] = True
                    for col, a in cand:
                        if a is None:
                            gb = float(gb_np[j])
                        else:
                            gb = float(np.abs(lv - a).sum(dtype=np.float32))
                        lab_t[p, col] = 1 if st["change"] > gb else 0
                if st["kind"] == "rnn":
                    post[p, Kp - k:, :] = st["seq_post"]
                    dgate[p] = True
                elif st["kind"] == "fallback":
                    fgate[p] = True
                    for col, a in cand:
                        if a is None:
                            ga = float(ga_np[j])
                        else:
                            ga = float(np.abs(blended[j] - a).sum(dtype=np.float32))
                        fb_t[p, col] = ga > 1.0 * st["scale"]
            finals[c], w = K.predictor_chain(
                pred_of(c)["params"], pre, post, lab_t, fb_t,
                lgate, dgate, fgate, Kp - k, 1e-2,
            )
            if any(s["kind"] == "rnn" for s in sub):
                wants_dev[c] = w

        used: dict[int, bool] = {}
        if rnn_any:
            w_host = fetch(wants_dev, "predictor")  # ONE blocking sync per window
            for c, wc in w_host.items():
                for p, st in enumerate(chains[c]):
                    if st["kind"] == "rnn":
                        used[st["j"]] = bool(wc[p])
        _, wants = resolve(used)
        new_params = {
            c: finals[c] for c in launch_cids
            if any(st["learn"] for st in chains[c])
        }
        return _PredictorPlan(wants=wants, new_params=new_params, launches=len(launch_cids))

    def _center_norm(self, cluster) -> float:
        """Post-blend center L1 norm for the guard's late check (per-event
        path: one host read per upload — the coalesced path gets the same
        scalar from the fused ``ingest_chain`` stats instead)."""
        if self.clustering.plane is None:
            return float(np.abs(fetch(tree_flat_vector(cluster.center), "center_norm")).sum())
        return float(np.abs(fetch(cluster.center_vec, "center_norm")).sum())

    def _rollback_center(self, cluster, branch, client_id) -> list[Downlink]:
        """Late detection fired: restore the newest finite last-known-good
        center (snapshot ring, then the broadcast anchor), record the
        recovery on the CI branch, and re-broadcast on demand — the
        paper-native recovery path (a broadcast with staleness accounting,
        not a new protocol). Every member, including the uploader whose
        blend was vetoed, re-syncs to the restored center."""
        cid = cluster.cluster_id
        if not cluster.rollback():
            # every recorded state is itself corrupt — nothing to restore;
            # the ledger still counts the detection
            self.guard.note_rollback()
            self.events.append({"kind": "rollback", "cluster": cid, "restored": False})
            return []
        self.guard.note_rollback()

        def merge_fn(head):
            cluster.version += 1
            return (
                cluster.center if self.clustering.plane is None else cluster.center_vec
            )

        branch.push(merge_fn)
        self.events.append({"kind": "rollback", "cluster": cid, "restored": True})
        return self._broadcast(cluster)

    def _broadcast(self, cluster, exclude: set = frozenset()) -> list[Downlink]:
        cluster.snapshot_broadcast()  # row copy in plane mode
        cluster.last_broadcast_version = cluster.version
        msgs = []
        for member in cluster.members - exclude:
            msgs.append(Downlink(member, cluster.center, cluster.version, cluster.cluster_id, "broadcast"))
            self.client_versions[member] = (cluster.cluster_id, cluster.version)
        self.events.append({"kind": "broadcast", "cluster": cluster.cluster_id, "n": len(msgs)})
        return msgs

    # ---------------------------------------------------------- refinement
    def _feedback_rows(self, pairs: list) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Stack feedback_fn outputs for (client, center) pairs. With a
        batched probe installed (``feedback_batch_fn``, e.g. the client
        fleet engine) the whole pair list is ONE model-evaluation launch;
        otherwise each pair probes via feedback_fn. Either way the chi2 x
        Var statistic downstream is one kernel launch."""
        if self.feedback_batch_fn is not None:
            f_pred, f_true, s_soft = self.feedback_batch_fn(list(pairs))
            return (
                np.asarray(f_pred),
                np.maximum(np.asarray(f_true), 1e-3),
                np.asarray(s_soft),
            )
        rows = [self.feedback_fn(m, center) for m, center in pairs]
        f_pred = np.stack([r[0] for r in rows])
        f_true = np.stack([np.maximum(r[1], 1e-3) for r in rows])
        s_soft = np.stack([r[2] for r in rows])
        return f_pred, f_true, s_soft

    def _collect_feedback(self) -> dict[int, dict[Any, float]]:
        """chi2 x Var(S) feedback for every member of every cluster, in one
        cluster-segmented kernel launch (the seed looped a launch per
        cluster). The same launch accumulates per-cluster sums of g, which
        become the cluster-mean feedback exposed in :meth:`stats`."""
        if self.feedback_fn is None:
            return {}
        cid_order = sorted(self.clustering.clusters)
        entries: list[tuple[int, int, Any, Any]] = []  # (segment, cid, member, center)
        for si, cid in enumerate(cid_order):
            cluster = self.clustering.clusters[cid]
            center = cluster.center  # materialized once per cluster
            for m in sorted(cluster.members):
                entries.append((si, cid, m, center))
        if not entries:
            return {}
        f_pred, f_true, s_soft = self._feedback_rows([(m, c) for _, _, m, c in entries])
        seg_ids = np.asarray([si for si, _, _, _ in entries], np.int32)
        g, seg_sum = K.chi2_feedback_all(
            f_pred, f_true, s_soft, seg_ids, num_segments=len(cid_order),
            **self.clustering._kernel_mesh_kwargs(len(entries)),
        )
        g, seg_sum = fetch((g, seg_sum), "chi2")
        counts = np.bincount(seg_ids, minlength=len(cid_order))
        self.last_cluster_feedback_mean = {
            cid: float(seg_sum[si] / counts[si])
            for si, cid in enumerate(cid_order)
            if counts[si] > 0  # empty clusters have no feedback, not g=0
        }
        per_cluster: dict[int, dict[Any, float]] = {}
        for (si, cid, m, _), gi in zip(entries, g.tolist()):
            per_cluster.setdefault(cid, {})[m] = gi
        return per_cluster

    def _reassign_by_feedback(self, feedback: dict[int, dict[Any, float]]) -> int:
        """A poor-fit member may simply belong to another *existing* cluster
        (on-arrival L1 assignment is fast but errorful — Sec. 4.2.2, and an
        upload stays geometrically closest to the center it trained from).
        Probe every flagged member's feedback against every other center in
        a single batched launch and move them to a decisively better fit."""
        clusters = self.clustering.clusters
        if self.feedback_fn is None or len(clusters) < 2:
            return 0
        flagged: list[tuple[Any, int, float]] = []  # (member, home cid, g)
        for cid, fb in feedback.items():
            if cid not in clusters or len(fb) < 2:
                continue
            med = float(np.median(list(fb.values())))
            for m, g in fb.items():
                if g <= 2.0 * (med + 1e-12):
                    continue
                if m in clusters[cid].partial_finetune:
                    continue
                flagged.append((m, cid, g))
        if not flagged:
            return 0
        centers = {cid: clusters[cid].center for cid in clusters}
        others_of = {
            home: [c2 for c2 in sorted(clusters) if c2 != home]
            for home in {home for _, home, _ in flagged}
        }
        pairs = [
            (m, centers[c2]) for m, home, _ in flagged for c2 in others_of[home]
        ]
        f_pred, f_true, s_soft = self._feedback_rows(pairs)
        # probe rows shard over the plane mesh once the flagged-member count
        # crosses mesh_min_rows (the single-device launch stays the default)
        scores = fetch(
            K.chi2_feedback(
                f_pred, f_true, s_soft,
                **self.clustering._kernel_mesh_kwargs(len(pairs)),
            ),
            "chi2",
        ).reshape(len(flagged), len(clusters) - 1)
        moves = 0
        for (m, home, g), row in zip(flagged, scores):
            best_i = int(np.argmin(row))
            if row[best_i] < 0.5 * g:
                best = others_of[home][best_i]
                self.clustering._move(m, best)
                self.client_versions[m] = (best, clusters[best].version)
                moves += 1
        return moves

    def _refine(self) -> list[Downlink]:
        out: list[Downlink] = []
        if not self.enable_clustering:
            return out
        n_events = len(self.events)
        with span("ingest/refine") as sp:
            self._refine_round += 1
            if self._refine_round % 5 == 0:  # decay peel counts so later data
                # drift (Fig. 18) can still split a previously-churned client out
                self.clustering.peel_counts = {
                    k: v - 1 for k, v in self.clustering.peel_counts.items() if v > 1
                }
            # lift head-only mode imposed before this refinement (Sec. 4.3.3:
            # "only be lifted after the next cluster merging refinement")
            for cluster in self.clustering.clusters.values():
                if cluster.partial_finetune and cluster.pf_round < self._refine_round - 1:
                    cluster.partial_finetune.clear()
            feedback = self._collect_feedback()

            # first try moving poor fits to an existing better-fitting cluster
            # (probe their feedback against every center); only the leftovers
            # (fit nowhere) justify spawning a new cluster
            moved = self._reassign_by_feedback(feedback)
            if moved:
                self.events.append({"kind": "reassign", "n": moved})
                feedback = self._collect_feedback()

            # expansion: split poor fits out of each cluster (last uploads are
            # plane rows in plane mode, pytrees otherwise)
            uploads = (
                self.last_uploads if self.clustering.plane is None else self._upload_rows
            )
            for cid, fb in list(feedback.items()):
                if cid not in self.clustering.clusters:
                    continue
                new_cid = self.clustering.expand(
                    cid, fb, uploads=uploads, refine_round=self._refine_round
                )
                if new_cid is not None:
                    parent_pred = self._predictor(cid)
                    new_cluster = self.clustering.clusters[new_cid]
                    change = max(fb.values()) if fb else 0.0
                    self.predictors[new_cid] = predictor_for_expansion(parent_pred, change)
                    self.repo.branch(f"cluster/{new_cid}", new_cluster.center)
                    self.events.append({"kind": "expand", "from": cid, "to": new_cid})
                    for m in new_cluster.members:
                        self.client_versions[m] = (new_cid, new_cluster.version)

            # merging: when cluster count exceeds hm * C, fold the nearest pair
            # when one is genuinely redundant; otherwise dissolve the smallest
            # cluster (refit its members) — blending two *distinct* centers just
            # to honor capacity creates the very staleness blob Sec. 4 avoids
            while self.clustering.should_merge():
                pair = self.clustering.nearest_pair()
                if pair is None:
                    if not self._dissolve_smallest():
                        break
                    continue
                a, b = pair
                pred_a, pred_b = self._predictor(a), self._predictor(b)  # before deletion
                train_fn = self.local_train_fn or (lambda p: p)
                merged_cid = self.clustering.merge_pair(a, b, train_fn)
                other = b if merged_cid == a else a
                pred = predictor_for_merge(pred_a, pred_b)
                self.predictors[merged_cid] = pred
                self.predictors.pop(other, None)
                self.repo.delete(f"cluster/{other}")
                self.repo.branch(f"cluster/{merged_cid}", self.clustering.clusters[merged_cid].center)
                self.events.append({"kind": "merge", "into": merged_cid, "from": other})
                # merged model is immediately broadcast (Sec. 5.2.2)
                out.extend(self._broadcast(self.clustering.clusters[merged_cid]))
            if tracing.on():
                kinds = Counter(e["kind"] for e in self.events[n_events:])
                sp.set_metadata(moved=moved, expansions=kinds["expand"], merges=kinds["merge"],
                                dissolves=kinds["dissolve"])
        return out

    def _dissolve_smallest(self) -> bool:
        """Capacity overflow with no redundant pair: retire the smallest
        cluster and refit each member to its best remaining cluster (by
        feedback probe when available, else by L1 of its last upload) —
        every probe for every member batched into a single launch."""
        clustering = self.clustering
        clusters = clustering.clusters
        if len(clusters) < 2:
            return False
        victim = min(clusters, key=lambda c: (clusters[c].size, clusters[c].version))
        rest = [c for c in clusters if c != victim]
        members = sorted(clusters[victim].members, key=str)
        best_of: dict[Any, int] = {m: rest[0] for m in members}
        plane = clustering.plane
        if members and self.feedback_fn is not None:
            centers = {c: clusters[c].center for c in rest}
            f_pred, f_true, s_soft = self._feedback_rows(
                [(m, centers[c]) for m in members for c in rest]
            )
            scores = fetch(
                K.chi2_feedback(
                    f_pred, f_true, s_soft,
                    **clustering._kernel_mesh_kwargs(len(f_pred)),
                ),
                "chi2",
            ).reshape(len(members), len(rest))
            for m, row in zip(members, scores):
                best_of[m] = rest[int(np.argmin(row))]
        elif members and plane is not None:
            have = [m for m in members if m in self._upload_rows]
            if have:
                kw = clustering._kernel_mesh_kwargs(len(have))
                # query rows go shard-local under a mesh (no one-device hop)
                # and uncached (one-shot set); the small center matrix stays
                # replicated
                U = plane.take([self._upload_rows[m] for m in have], on_mesh="shard" if kw else False)
                centers = plane.rows([clusters[c]._row for c in rest], on_mesh=bool(kw))
                D = fetch(K.l1_distance_pairwise(U, centers, **kw), "pairwise_l1")
                for m, d in zip(have, D):
                    best_of[m] = rest[int(np.argmin(d))]
        elif members:
            with_uploads = [m for m in members if m in self.last_uploads]
            if with_uploads:
                centers = jnp.stack([tree_flat_vector(clusters[c].center) for c in rest])
                U = jnp.stack([tree_flat_vector(self.last_uploads[m]) for m in with_uploads])
                D = fetch(K.l1_distance_pairwise(U, centers), "pairwise_l1")
                for m, d in zip(with_uploads, D):
                    best_of[m] = rest[int(np.argmin(d))]
        for m in members:
            best = best_of[m]
            clustering._move(m, best)
            self.client_versions[m] = (best, clusters[best].version)
        clustering.drop_cluster(victim)
        self.predictors.pop(victim, None)
        self.repo.delete(f"cluster/{victim}")
        self.events.append({"kind": "dissolve", "cluster": victim})
        return True

    # --------------------------------------------------- elastic eviction
    def evict_clients(self, client_ids: list) -> dict:
        """Administratively remove clients that have gone permanently dark
        (device death under fault injection, or a drop-the-straggler
        policy giving up on them). Frees each client's upload row, drops
        its assignment/version bookkeeping, and — when a cluster's
        membership empties — reclaims the cluster itself: center and
        broadcast-anchor rows go back to the plane free-list, the
        predictor and CI branch are deleted. Without this, every
        all-members-dark cluster would leak two plane rows (plus one per
        member upload) for the rest of the run.

        Returns ``{"evicted": [...], "reclaimed": [cluster ids]}``."""
        cl = self.clustering
        evicted: list = []
        reclaimed: list[int] = []
        for client_id in client_ids:
            touched = False
            if self.uplink_codec is not None:
                # dead clients never upload again: their codec anchor (+ EF
                # residual) rows go back to the codec plane's free list
                self.uplink_codec.release_client(client_id)
            row = self._upload_rows.pop(client_id, None)
            if row is not None:
                cl.plane.free(row)
                touched = True
            if self.last_uploads.pop(client_id, None) is not None:
                touched = True
            self.client_versions.pop(client_id, None)
            home = cl.assignment.pop(client_id, None)
            if home is not None and home in cl.clusters:
                touched = True
                cluster = cl.clusters[home]
                cluster.members.discard(client_id)
                cluster.partial_finetune.discard(client_id)
                # reclaiming cluster 0 would break the clustering-off
                # ablation, which hardwires every upload into it
                if not cluster.members and self.enable_clustering:
                    cl.drop_cluster(home)
                    self.predictors.pop(home, None)
                    self.repo.delete(f"cluster/{home}")
                    reclaimed.append(home)
            if touched:
                evicted.append(client_id)
                self.events.append({"kind": "evict", "client": str(client_id)})
        for home in reclaimed:
            self.events.append({"kind": "reclaim", "cluster": home})
        return {"evicted": evicted, "reclaimed": reclaimed}

    # ------------------------------------------------ checkpoint/restart
    def state_dict(self) -> tuple[PyTree, dict]:
        """(array_tree, json_meta) capturing every piece of server state the
        paper's protocol accumulates: cluster centers + broadcast anchors,
        per-cluster RNN predictor weights, Top-K records, membership,
        versions, staleness counters. Restore with :meth:`load_state`."""
        cl = self.clustering
        # per-client last uploads: the dissolve/expand refinement geometry.
        # Without them a restarted server silently refines blind (every
        # member probes as its cluster center) until each client re-uploads.
        if cl.plane is None:
            last_uploads = {str(k): v for k, v in self.last_uploads.items()}
        else:
            last_uploads = {
                str(k): cl.plane.to_pytree(row) for k, row in self._upload_rows.items()
            }
        tree = {
            "centers": {str(cid): c.center for cid, c in cl.clusters.items()},
            "bcast_centers": {
                str(cid): c.last_broadcast_center for cid, c in cl.clusters.items()
            },
            "last_uploads": last_uploads,
            "rnn": {str(cid): p.params for cid, p in self.predictors.items()},
        }
        meta = {
            "clusters": {
                str(cid): {
                    "version": c.version,
                    "members": sorted(map(str, c.members)),
                    "partial_finetune": sorted(map(str, c.partial_finetune)),
                    "pf_round": c.pf_round,
                    "last_broadcast_version": c.last_broadcast_version,
                }
                for cid, c in cl.clusters.items()
            },
            "assignment": {str(k): v for k, v in cl.assignment.items()},
            "next_id": cl._next_id,
            "merges": cl.merges,
            "expansions": cl.expansions,
            "peel_counts": {str(k): v for k, v in cl.peel_counts.items()},
            "predictors": {
                str(cid): {
                    "k": p.k, "records": p.records, "active": p.active,
                    "scale": p.scale, "decisions": p.decisions, "broadcasts": p.broadcasts,
                }
                for cid, p in self.predictors.items()
            },
            "staleness": {
                "count": self.staleness.count,
                "total": self.staleness.total,
                "q_max": self.staleness.q_max,
            },
            "client_versions": {str(k): list(v) for k, v in self.client_versions.items()},
            "uploads": self._uploads,
            "decisions": self._decisions,
            "rnn_broadcasts": self._rnn_broadcasts,
            "refine_round": self._refine_round,
            "upload_clients": sorted(last_uploads),
            # exact-restart extras: the expand cooldown gates refinement
            # decisions, and events/feedback means feed stats() — a mid-run
            # kill+restore must reproduce the uninterrupted ledger exactly
            "last_expand_round": {str(k): v for k, v in cl._last_expand_round.items()},
            "events": list(self.events),
            "cluster_feedback_mean": {
                str(k): v for k, v in self.last_cluster_feedback_mean.items()
            },
        }
        if self.uplink_codec is not None:
            # compressed-uplink codec state (anchors + EF residuals): without
            # it a restarted run re-anchors at zero and the first post-restart
            # upload per client ships a full-model delta through the codec
            tree["uplink"], meta["uplink"] = self.uplink_codec.state_dict()
        return tree, meta

    def state_template(self, meta: dict) -> PyTree:
        """Tree-structure template matching :meth:`state_dict` for ``meta`` —
        lets the checkpointer restore without pickling (centers share the
        init_params structure; predictors share the RNN structure)."""
        from repro.core.broadcast import init_rnn

        rnn_like = self._rnn_init if self._rnn_init is not None else init_rnn(jax.random.PRNGKey(0))
        template = {
            "centers": {cid: self.init_params for cid in meta["clusters"]},
            "bcast_centers": {cid: self.init_params for cid in meta["clusters"]},
            "last_uploads": {c: self.init_params for c in meta.get("upload_clients", [])},
            "rnn": {cid: rnn_like for cid in meta["predictors"]},
        }
        if meta.get("uplink"):
            from repro.fl.uplink import seed_template

            template["uplink"] = seed_template(meta["uplink"], self.init_params)
        return template

    def load_state(self, tree: PyTree, meta: dict, client_id_type=int) -> None:
        """Restore from :meth:`state_dict` output (elastic restart)."""
        cid_of = lambda s: client_id_type(s)
        cl = self.clustering
        if cl.plane is not None:  # return pre-restore upload rows too
            for row in self._upload_rows.values():
                cl.plane.free(row)
        self._upload_rows = {}
        self.last_uploads = {}
        cl.reset()  # frees any live plane rows before adopting the snapshot
        for cid_s, info in meta["clusters"].items():
            cid = int(cid_s)
            c = cl.restore_cluster(cid, tree["centers"][cid_s], tree["bcast_centers"][cid_s])
            c.version = info["version"]
            c.members = {cid_of(m) for m in info["members"]}
            c.partial_finetune = {cid_of(m) for m in info["partial_finetune"]}
            c.pf_round = info["pf_round"]
            c.last_broadcast_version = info["last_broadcast_version"]
            self.repo.branch(f"cluster/{cid}", c.center)
        # restore per-client last uploads (absent in pre-upload_clients
        # checkpoints: refinement then runs without last-upload geometry —
        # no dissolve/expand seeding — until every client re-uploads)
        for k, v in (tree.get("last_uploads") or {}).items():
            if cl.backend == "plane":
                cl._ensure_plane(v)
                self._upload_rows[cid_of(k)] = cl.plane.alloc(v)
            else:
                self.last_uploads[cid_of(k)] = v
        cl.assignment = {cid_of(k): v for k, v in meta["assignment"].items()}
        cl._next_id = meta["next_id"]
        cl.merges = meta["merges"]
        cl.expansions = meta["expansions"]
        cl.peel_counts = {cid_of(k): v for k, v in meta["peel_counts"].items()}
        self.predictors = {}
        for cid_s, info in meta["predictors"].items():
            p = BroadcastPredictor(params=tree["rnn"][cid_s], k=info["k"])
            p.records = list(info["records"])
            p.active = info["active"]
            p.scale = info["scale"]
            p.decisions = info["decisions"]
            p.broadcasts = info["broadcasts"]
            self.predictors[int(cid_s)] = p
        st = meta["staleness"]
        self.staleness.count, self.staleness.total, self.staleness.q_max = (
            st["count"], st["total"], st["q_max"],
        )
        self.client_versions = {cid_of(k): tuple(v) for k, v in meta["client_versions"].items()}
        self._uploads = meta["uploads"]
        self._decisions = meta["decisions"]
        self._rnn_broadcasts = meta["rnn_broadcasts"]
        self._refine_round = meta["refine_round"]
        # exact-restart extras (absent in older checkpoints: cooldowns and
        # stats counters then restart empty, which older callers tolerated)
        cl._last_expand_round = {
            int(k): v for k, v in meta.get("last_expand_round", {}).items()
        }
        self.events = list(meta.get("events", []))
        self.last_cluster_feedback_mean = {
            int(k): v for k, v in meta.get("cluster_feedback_mean", {}).items()
        }
        if meta.get("uplink"):
            if self.uplink_codec is not None:
                self.uplink_codec.load_state(tree["uplink"], meta["uplink"], client_id_type)
                self._pending_uplink_state = None
            else:
                # the codec builds with the next run's fleet; replay then
                self._pending_uplink_state = (tree["uplink"], meta["uplink"], client_id_type)
        else:
            self._pending_uplink_state = None

    # ------------------------------------------------------------- metrics
    def stats(self) -> dict:
        plane = self.clustering.plane
        return {
            "clusters": len(self.clustering.clusters),
            "merges": self.clustering.merges,
            "expansions": self.clustering.expansions,
            "staleness": self.staleness.snapshot(),
            "broadcasts": sum(1 for e in self.events if e["kind"] == "broadcast"),
            "rnn_broadcasts": self._rnn_broadcasts,
            "decisions": self._decisions,
            "backend": self.clustering.backend,
            "plane_rows": 0 if plane is None else plane.num_allocated,
            # snapshot from the last refine, filtered to clusters still alive
            "cluster_feedback_mean": {
                cid: g
                for cid, g in self.last_cluster_feedback_mean.items()
                if cid in self.clustering.clusters
            },
        }
