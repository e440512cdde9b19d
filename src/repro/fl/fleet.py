"""Device-resident client fleet engine: the *client* side of the simulator
as batched matrix compute.

PRs 1-2 made the server hot path device-resident (the parameter plane);
this module does the same for the simulated devices. The seed simulator
dispatched one ``_sgd_epoch`` jit call per client per epoch, one
``evaluate`` launch per client per eval tick, and one
``predict_distributions`` probe per (member, center) feedback pair —
O(clients) Python-loop dispatches for work that is embarrassingly
batchable. The fleet engine replaces those loops with three fused
launches:

* :meth:`ClientFleet.train_cohort` / :meth:`ClientFleet.train_client` —
  ``jax.vmap`` over clients of a ``lax.scan`` over epochs (the task's
  ``fleet_local_train``). Per-client ``lr`` / ``epochs`` / ``head_only``
  are vmapped operands, so heterogeneous epoch budgets and partial
  fine-tuning stay per-row.
* :meth:`ClientFleet.evaluate_fleet` — one masked-accuracy launch for the
  whole fleet per eval tick.
* :meth:`ClientFleet.feedback_many` — batched ``predict_distributions``
  emitting ``(pairs, num_classes)`` F/S stacks that feed the server's
  ``chi2_feedback_all`` kernel directly.

State layout mirrors the server plane: every client's current model is a
row of a second :class:`~repro.core.plane.ParameterPlane` (a non-cluster
row namespace), and each client additionally owns an *evaluation-view* row
holding the last parameters it was evaluated with — refreshed only when
the strategy hands a different object, so the per-tick eval gather is the
plane's incrementally-patched cached view (O(changed rows), not O(fleet)).
Per-client train/test data pads into ``(clients, n, dim)`` device tensors
with validity masks; ragged datasets are handled by masking, which keeps
padded rows out of losses, accuracies, and histograms. A replaced
``SimClient.data`` (distribution drift) is detected by identity check at
every launch and triggers a tensor rebuild, matching the loop backend's
live-read semantics.

Cohort launches pad to the next power of two (extra rows get a zero epoch
budget), so the jit cache holds O(log clients) entries instead of one per
cohort size, and the dispatch count stays flat as the fleet grows.

The task's ``operands`` (a frozen base) ride every launch as an argument.
A launch whose cohort would not fit the device runs it in client blocks, a
``lax.map`` over blocks inside the one launch; the block is the largest
power of two of clients whose activations (``task.client_bytes``) fit half
of the device's memory (the rest holds the base, the planes and the
launch's operands), so a small model's cohort stays one block and its
program is the unblocked one.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.common.pytrees import FlattenSpec, flatten_spec
from repro.common.tracing import fetch, span
from repro.core.plane import ParameterPlane
from repro.fl.tasks import MLP_TASK

PyTree = Any


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@functools.cache
def _device_bytes() -> int | None:
    """The first device's memory, where its backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _block(task, kind: str, data: dict, rows: int) -> int:
    """Clients per block of a ``kind`` launch over ``rows`` clients: the
    largest power of two whose activations fit half the device's memory,
    or all ``rows`` where they fit or the device reports none."""
    limit = _device_bytes()
    per = task.client_bytes(kind, data)
    if limit is None or rows * per <= limit // 2:
        return rows
    return max(1, 1 << ((limit // 2) // max(per, 1)).bit_length() - 1)


def _blocked(fn, block: int, *args):
    """``fn(*args)`` over leaves with a leading row axis, in blocks of
    ``block`` rows (a ``lax.map``; the last block padded with row 0); one
    block calls ``fn`` directly."""
    rows = jax.tree_util.tree_leaves(args)[0].shape[0]
    if block >= rows:
        return fn(*args)
    nb = -(-rows // block)

    def split(x):
        if nb * block > rows:
            x = jnp.concatenate([x, jnp.broadcast_to(x[:1], (nb * block - rows, *x.shape[1:]))])
        return x.reshape(nb, block, *x.shape[1:])

    out = lax.map(lambda a: fn(*a), jax.tree_util.tree_map(split, args))
    return jax.tree_util.tree_map(lambda x: x.reshape(nb * block, *x.shape[2:])[:rows], out)


@functools.partial(jax.jit, static_argnames=("spec", "max_epochs", "task", "block"))
def _train_launch(mat, train, gather, lr, epochs, head, ops, *,
                  spec: FlattenSpec, max_epochs: int, task, block: int):
    # the cohort's data-row gather happens inside the launch, fused with the
    # training compute — no materialized (P, n, ...) copies per round. The
    # whole train dict is gathered; tensors the task never reads (e.g. the
    # MLP feedback path ignoring labels) are pruned by XLA DCE.
    d = {k: v[gather] for k, v in train.items()}

    def run(mat, d, lr, epochs, head):
        params_b = jax.vmap(spec._unflatten)(mat)
        new_b, losses, counts = task.fleet_local_train(
            ops, params_b, d, lr, epochs, head, max_epochs=max_epochs
        )
        return jax.vmap(spec._flatten)(new_b), losses, counts

    return _blocked(run, block, mat, d, lr, epochs, head)


@functools.partial(jax.jit, static_argnames=("spec", "max_epochs", "task", "block"))
def _train_launch_bank(bank, sel, train, gather, lr, epochs, head, ops, *,
                       spec: FlattenSpec, max_epochs: int, task, block: int):
    # row-sliced variant: the model matrix is gathered from the fleet's
    # model-row bank INSIDE the launch. An eager per-call gather of dozens
    # of scattered plane rows is the slow path on CPU (that is why the
    # plane caches views); in-jit it compiles once and fuses with training.
    return _train_launch.__wrapped__(
        bank[sel], train, gather, lr, epochs, head, ops,
        spec=spec, max_epochs=max_epochs, task=task, block=block,
    )


@functools.partial(jax.jit, static_argnames=("spec", "task", "block"))
def _eval_launch(mat, test, ops, *, spec: FlattenSpec, task, block: int):
    return _blocked(lambda m, t: task.fleet_evaluate(ops, jax.vmap(spec._unflatten)(m), t), block, mat, test)


# A downlink batch fans a few distinct payloads out over many rows. Its
# (rows, dim) matrix is gathered in one launch from a bank of the distinct
# payloads: the host payloads' rows, then the device payloads' vectors. Each
# part holds at least _BANK_MIN slots (a power of two above), so one program
# serves every batch of a row count that holds a handful of each kind.
_BANK_MIN = 8

_ROW = lax.GatherDimensionNumbers(offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))


@jax.jit
def _install_rows(host, devs, sel):
    # plain lax: jnp's indexing traces helper jits for every new shape
    bank = lax.concatenate(
        [host, *(lax.expand_dims(lax.convert_element_type(v, host.dtype), (0,)) for v in devs)], 0
    )
    return lax.gather(bank, sel[:, None], _ROW, (1, bank.shape[1]),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _bank(n: int) -> int:
    return max(_BANK_MIN, _pow2(n))


def _host_leaves(params: PyTree) -> list | None:
    """The leaves of a payload held in host memory (every leaf a NumPy
    array, like the unicasts the server builds with ``unflatten_np``), else
    None."""
    leaves = jax.tree_util.tree_leaves(params)
    if leaves and all(isinstance(x, np.ndarray) for x in leaves):
        return leaves
    return None


@functools.partial(jax.jit, static_argnames=("spec", "num_classes", "task", "block"))
def _feedback_launch(bank, sel, train, gather, ops, *, spec: FlattenSpec,
                     num_classes: int, task, block: int):
    # a probe sweep pairs hundreds of members against a handful of DISTINCT
    # centers: the (pairs, dim) probe matrix is expanded from the small
    # center bank inside the launch, never materialized eagerly (a block's
    # probes and data rows are gathered inside its step)
    def run(sel, gather):
        mat = bank[sel]
        d = {k: v[gather] for k, v in train.items()}
        return task.fleet_feedback(ops, jax.vmap(spec._unflatten)(mat), d, num_classes)

    return _blocked(run, block, sel, gather)


class ClientFleet:
    """Batched state + fused launches for a list of :class:`SimClient`s.

    With ``mesh`` (or the ``REPRO_FLEET_MESH`` env knob), the fleet's
    client-model plane AND its ``(clients, n, dim)`` data tensors place
    over the mesh's ``plane`` (row) axis — batched training/eval launches
    then shard over simulated devices the same way the server plane's
    kernels already do, instead of pinning the whole fleet's models and
    datasets to one accelerator. Per-client arithmetic is unchanged (the
    launches are client-wise vmaps), so trajectories do not depend on the
    mesh."""

    def __init__(self, clients: Sequence[Any], template: PyTree, *,
                 mesh: Any | None = None, task: Any | None = None):
        self.clients = list(clients)
        self.ids = [c.client_id for c in self.clients]
        self.index = {cid: i for i, cid in enumerate(self.ids)}
        K = len(self.clients)
        self.num_classes = self.clients[0].num_classes
        # the fleet's task: explicit arg, else the clients' own, else MLP.
        # All clients must share one task (one fused launch per fleet).
        self.task = task or getattr(self.clients[0], "task", None) or MLP_TASK
        self.spec = flatten_spec(template)
        if mesh is None:
            from repro.launch.mesh import fleet_mesh_from_env

            mesh = fleet_mesh_from_env()
        elif mesh is False:
            mesh = None
        if mesh is not None and K % mesh.shape["plane"] != 0:
            # the (clients, n, dim) tensors place with an eager device_put,
            # which (unlike jit outputs) cannot pad a non-divisible leading
            # dim — a fleet that does not divide the row shards runs
            # single-device, like the un-meshed default
            mesh = None
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # (clients, ...) tensors of any rank shard over the row axis
            self._dim_shardings: dict[int, Any] = {}
            self._sharding_of = lambda ndim: self._dim_shardings.setdefault(
                ndim, NamedSharding(mesh, PartitionSpec("plane", *(None,) * (ndim - 1)))
            )
            self._replicated = NamedSharding(mesh, PartitionSpec())
        # the task's device operands, placed once (replicated under a mesh)
        self._ops = jax.tree_util.tree_map(self._rep, self.task.operands)
        self.plane = ParameterPlane(template, capacity=2 * K, mesh=mesh)
        self._model_row = [self.plane.alloc() for _ in range(K)]
        self._eval_row = [self.plane.alloc() for _ in range(K)]
        self._has_model = [False] * K
        # monotonic per-client model-row version (bumped on every write), so
        # the eval rows can tell whether a mirrored model row went stale
        self._model_ver = [0] * K
        # what each eval row currently holds: the exact params object last
        # written (identity-compared), or a ("model", version) tag when it
        # mirrors the client's own model row
        self._eval_src: list[Any] = [object()] * K

        self._build_data()
        # pytree -> flat-vector memo, keyed by object identity (the held
        # reference keeps the id stable). Strategies hand the *same* center
        # object to every member, so a broadcast costs one flatten total.
        self._flat_cache: dict[int, tuple[Any, jax.Array]] = {}
        self.launches = 0  # fused launches issued (bench introspection)

    # ----------------------------------------------------------- data plane
    def _shard_clients(self, x: jax.Array) -> jax.Array:
        """Place a (clients, ...) tensor sharded over the fleet mesh's row
        axis (no-op without a mesh)."""
        if self.mesh is None:
            return x
        return jax.device_put(x, self._sharding_of(x.ndim))

    def _rep(self, x) -> jax.Array:
        """Replicate a small launch operand (a stacked model matrix, gather
        indices, per-row hyperparams) over the fleet mesh so it can share a
        jit with the client-sharded data tensors (no-op without a mesh)."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._replicated)

    def _build_data(self) -> None:
        """(Re)pad every client's train/test split into the task's batched
        device tensors, and cache the true label histograms."""
        self._data_ref = [c.data for c in self.clients]
        fd = self.task.build_fleet_data(
            self._data_ref, self._shard_clients, self.num_classes
        )
        self._train_data = fd.train
        self._test_data = fd.test
        self.f_true = fd.f_true
        self._tokens = fd.tokens

    def _sync_data(self) -> None:
        """Match the loop backend's live-read semantics: a replaced
        ``SimClient.data`` (distribution drift, Fig. 18 style) triggers a
        rebuild of the batched tensors. The steady-state cost is K identity
        checks per launch; the rebuild itself only runs on an actual swap."""
        for c, ref in zip(self.clients, self._data_ref):
            if c.data is not ref:
                self._build_data()
                return

    # ------------------------------------------------------------ adapters
    def _vec_of(self, params: PyTree) -> jax.Array:
        if isinstance(params, jax.Array) and params.ndim == 1:
            return params
        key = id(params)
        hit = self._flat_cache.pop(key, None)  # pop + reinsert: LRU on hit
        if hit is not None and hit[0] is params:
            self._flat_cache[key] = hit
            return hit[1]
        vec = self.spec.flatten(params)
        if len(self._flat_cache) >= 512:  # evict the LRU entry only — the
            # hot working set (live centers, the global model) stays cached
            self._flat_cache.pop(next(iter(self._flat_cache)))
        self._flat_cache[key] = (params, vec)
        return vec

    def to_pytree_np(self, vec: np.ndarray) -> PyTree:
        """Host-side unflatten (numpy views, zero device dispatches) for
        fanning a batched training result back out into per-client pytrees."""
        return self.spec.unflatten_np(vec)

    # ------------------------------------------------------------- models
    def set_model(self, cid, params: PyTree) -> None:
        i = self.index[cid]
        with span("install", rows=1, distinct=1):
            self.plane.write(self._model_row[i], self._vec_of(params))
        self._has_model[i] = True
        self._model_ver[i] += 1

    def set_models(self, cids: Sequence[Any], params_list: Sequence[PyTree]) -> None:
        """Install a batch of downlinked models in one staged write.
        Duplicate clients keep the LAST entry, matching sequential
        ``set_model`` overwrite order.

        A batch carries few distinct payload objects (a broadcast fans one
        center out to every member), so the ``(rows, dim)`` matrix is built
        from the distinct payloads and a row -> payload index: host payloads
        flatten in NumPy into one block (one transfer), device payloads go
        through the identity-cached ``_vec_of``, and one launch gathers the
        rows — bitwise the rows sequential ``set_model`` calls would
        stage."""
        latest: dict[int, PyTree] = {}
        for cid, p in zip(cids, params_list):
            latest[self.index[cid]] = p
        with span("install", rows=len(latest)) as sp:
            with span("install/flatten"):
                slot: dict[int, int] = {}  # id(payload) -> host slot, or ~device slot
                host: list = []
                devs: list = []
                for p in latest.values():
                    if id(p) not in slot:
                        leaves = _host_leaves(p)
                        if leaves is None:
                            slot[id(p)] = ~len(devs)
                            devs.append(self._vec_of(p))
                        else:
                            slot[id(p)] = len(host)
                            host.append(np.concatenate([np.ravel(x) for x in leaves]))
                sp.set_metadata(distinct=len(slot))
                block = np.zeros((_bank(len(host)), self.spec.dim), self.plane.dtype)
                for row, vec in zip(block, host):
                    row[:] = vec
                sel = np.array([slot[id(p)] for p in latest.values()], np.int32)
                sel = np.where(sel >= 0, sel, len(block) + ~sel).astype(np.int32)
                devs += devs[:1] * (_bank(len(devs)) - len(devs))
                mat = _install_rows(block, tuple(devs), sel)
            self.plane.write_rows([self._model_row[i] for i in latest], mat)
        for i in latest:
            self._has_model[i] = True
            self._model_ver[i] += 1

    def model_vec(self, cid) -> jax.Array:
        i = self.index[cid]
        if not self._has_model[i]:
            # the loop path (SimClient.local_train with model=None) fails
            # loudly too — never train from the zero-seeded row silently
            raise ValueError(f"client {cid} has no model set")
        return self.plane.row(self._model_row[i])

    # ------------------------------------------------------------ training
    def _train_specs(self, cids: Sequence[Any]):
        cs = [self.clients[self.index[c]] for c in cids]
        lr = np.asarray([c.lr for c in cs], np.float32)
        epochs = np.asarray([c.local_epochs for c in cs], np.int32)
        head = np.asarray([1.0 if c.partial_finetune else 0.0 for c in cs], np.float32)
        return lr, epochs, head

    def _train(self, idx: np.ndarray, mat: jax.Array | None, lr, epochs, head, *,
               bank: jax.Array | None = None):
        """Shared padded launch: returns device (S, dim) vecs, (S,) losses
        and the task's per-client counters. ``mat`` is an explicit (S, dim)
        model matrix; alternatively pass ``bank`` (the full model-row view)
        and the rows ``idx`` select are gathered inside the launch."""
        self._sync_data()
        S = len(idx)
        P = _pow2(S)
        if P != S:
            idx = np.concatenate([idx, np.full(P - S, idx[0])])
            if mat is not None:
                mat = jnp.concatenate([mat, jnp.broadcast_to(mat[:1], (P - S, mat.shape[1]))])
            lr = np.concatenate([lr, np.zeros(P - S, np.float32)])
            epochs = np.concatenate([epochs, np.zeros(P - S, np.int32)])  # padded rows train 0 epochs
            head = np.concatenate([head, np.zeros(P - S, np.float32)])
        max_epochs = int(epochs.max()) if len(epochs) else 0
        self.launches += 1
        args = (
            self._train_data,
            self._rep(idx),
            self._rep(lr),
            self._rep(epochs),
            self._rep(head),
            self._ops,
        )
        kw = dict(spec=self.spec, max_epochs=max_epochs, task=self.task,
                  block=_block(self.task, "train", self._train_data, P))
        if bank is not None:
            vecs, losses, counts = _train_launch_bank(self._rep(bank), self._rep(idx), *args, **kw)
        else:
            vecs, losses, counts = _train_launch(self._rep(mat), *args, **kw)
        return vecs[:S], losses[:S], {k: v[:S] for k, v in counts.items()}

    def _trained_tokens(self, idx, epochs) -> int:
        """Tokens the clients ``idx`` train in ``epochs`` (a task that
        trains on tokens)."""
        return int(np.sum(self._tokens[np.asarray(idx)] * np.asarray(epochs)))

    @staticmethod
    def _record_counts(counts: dict) -> None:
        """The launch's fetched counters as zero-length spans: the pairs its
        forward passes routed to held experts, and its expert layer calls,
        those that ran the smallest pair buffer and the buffers' rows."""
        if "routed" in counts:
            with span("train/routed", pairs=int(np.sum(counts["routed"])),
                      **{k: int(np.sum(counts[k])) for k in ("calls", "small", "rows")}):
                pass

    def train_cohort(
        self, cids: Sequence[Any], params_list: Sequence[PyTree], *,
        with_vecs: bool = False,
    ):
        """One fused launch of local training for a selected cohort (the
        sync-round path). ``params_list[i]`` is what client ``cids[i]``
        trains from; ``None`` falls back to the client's own model row
        (the same contract as ``SimClient.local_train(None)``). Returns
        (per-client trained pytrees, losses) — plus the device ``(S, dim)``
        trained matrix when ``with_vecs`` is set, so a downstream batched
        consumer (the uplink codec) can launch on it directly instead of
        re-flattening S pytrees."""
        idx = np.asarray([self.index[c] for c in cids])
        mat = jnp.stack([
            self.model_vec(c) if p is None else self._vec_of(p)
            for c, p in zip(cids, params_list)
        ])
        vecs, losses, counts = self._train(idx, mat, *self._train_specs(cids))
        vecs_np, losses_np, counts = fetch((vecs, losses, counts), "fleet_train")
        self._record_counts(counts)
        # the per-client leaves are views over this one base matrix: freeze
        # it so an (unsupported) in-place mutation raises, exactly like the
        # immutable jax-array leaves the loop path hands out
        vecs_np = np.asarray(vecs_np)
        vecs_np.flags.writeable = False
        out = [self.to_pytree_np(v) for v in vecs_np], losses_np
        return (*out, vecs) if with_vecs else out

    def train_client(self, cid) -> tuple[PyTree, jax.Array]:
        """Row-sliced single-client path (the async event loop): trains from
        this client's model row, writes the new row back, and returns the
        updated params as a pytree plus the device-scalar loss."""
        i = self.index[cid]
        specs = self._train_specs([cid])
        with span("train/launch", rows=1) as sp:
            if self._tokens is not None:
                sp.set_metadata(tokens=self._trained_tokens([i], specs[1]))
            mat = self.model_vec(cid)[None, :]
            vecs, losses, _ = self._train(np.asarray([i]), mat, *specs)
        vec = vecs[0]
        self.plane.write(self._model_row[i], vec)
        self._has_model[i] = True
        self._model_ver[i] += 1
        return self.spec.unflatten(vec), losses[0]

    def train_rows(self, cids: Sequence[Any], *, with_vecs: bool = False):
        """Row-sliced BATCH of the async path: N concurrent ``upload_start``
        events become one fused launch. Every client trains from (and
        writes back) its own model row — exactly N :meth:`train_client`
        calls' arithmetic, since the rows are mutually independent — and
        the trained models come back as host-side numpy-view pytrees plus
        the (N,) losses (and, with ``with_vecs``, the device ``(N, dim)``
        trained matrix for batched downstream consumers like the uplink
        codec). ``cids`` must be distinct (one in-flight local round per
        client, which the event loop guarantees)."""
        idx = np.asarray([self.index[c] for c in cids])
        for c in cids:
            if not self._has_model[self.index[c]]:
                raise ValueError(f"client {c} has no model set")
        # the model-row bank is a hot cached view (downlink writes patch it
        # incrementally); the batch's rows are gathered from it inside the
        # launch — an eager scattered-row gather per window is the slow
        # path on CPU
        specs = self._train_specs(cids)
        with span("train/launch", rows=len(cids)) as sp:
            if self._tokens is not None:
                sp.set_metadata(tokens=self._trained_tokens(idx, specs[1]))
            bank = self.plane.rows(tuple(self._model_row))
            vecs, losses, counts = self._train(idx, None, *specs, bank=bank)
        self.plane.write_rows([self._model_row[i] for i in idx], vecs)
        for i in idx:
            self._has_model[i] = True
            self._model_ver[i] += 1
        vecs_np, losses_np, counts = fetch((vecs, losses, counts), "fleet_train")
        self._record_counts(counts)
        vecs_np = np.asarray(vecs_np)
        vecs_np.flags.writeable = False  # leaves are views: freeze like train_cohort
        out = [self.to_pytree_np(v) for v in vecs_np], losses_np
        return (*out, vecs) if with_vecs else out

    # ---------------------------------------------------------- evaluation
    def evaluate_fleet(self, params_list: Sequence[PyTree | None]) -> np.ndarray:
        """(K,) accuracies in fleet order, one launch. ``params_list[i]`` is
        the pytree client ``i`` evaluates (identity-cached into its eval
        row); ``None`` falls back to the client's own model row — or 0.0
        when no model was ever set, matching the per-client loop path."""
        self._sync_data()
        plane = self.plane
        zero = np.zeros(len(self.ids), bool)
        refresh_rows: list[int] = []
        refresh_vecs: list[jax.Array] = []
        for i, obj in enumerate(params_list):
            if obj is None:
                if not self._has_model[i]:
                    zero[i] = True
                    continue
                tag = ("model", self._model_ver[i])
                src = self._eval_src[i]
                if not (isinstance(src, tuple) and src == tag):  # mirror stale
                    plane.copy_row(self._model_row[i], self._eval_row[i])
                    self._eval_src[i] = tag
            elif self._eval_src[i] is not obj:
                refresh_rows.append(self._eval_row[i])
                refresh_vecs.append(self._vec_of(obj))
                self._eval_src[i] = obj
        if refresh_rows:
            # one bulk staging entry for the whole refresh (a broadcast can
            # change most of the fleet's eval params in one tick)
            plane.write_rows(refresh_rows, jnp.stack(refresh_vecs))
        # cached view, patched in place (mesh-replicated under a fleet mesh
        # so it can share the launch with the client-sharded data tensors)
        mat = plane.rows(tuple(self._eval_row), on_mesh=self.mesh is not None)
        self.launches += 1
        block = _block(self.task, "eval", self._test_data, mat.shape[0])
        accs = fetch(_eval_launch(mat, self._test_data, self._ops, spec=self.spec, task=self.task,
                                  block=block), "eval")
        if zero.any():
            accs = np.where(zero, 0.0, accs)
        return accs

    # ------------------------------------------------------------ feedback
    def feedback_many(
        self, pairs: Sequence[tuple[Any, PyTree]]
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Batched (member, center) feedback probes: one launch emitting the
        stacked (F_pred, F_true, S_soft) rows the server's segmented chi2
        kernel consumes — a drop-in for ``EchoPFLServer.feedback_batch_fn``."""
        self._sync_data()
        idx = np.asarray([self.index[m] for m, _ in pairs])
        # distinct centers only (a sweep probes every member against the
        # same few cluster centers): stack the small bank, expand in-launch
        bank_ids: dict[int, int] = {}
        bank_vecs: list[jax.Array] = []
        sel = np.empty(len(pairs), np.int32)
        for k, (_, center) in enumerate(pairs):
            key = id(center)
            slot = bank_ids.get(key)
            if slot is None:
                slot = bank_ids[key] = len(bank_vecs)
                bank_vecs.append(self._vec_of(center))
            sel[k] = slot
        M = len(pairs)
        P = _pow2(M)
        block = _block(self.task, "feedback", self._train_data, P)
        # pow2-padded bank: O(log centers) jit cache; a launch too large
        # for one block (a costly compile) pads to at least 8, one program
        # per probe count
        B = _pow2(len(bank_vecs)) if block >= P else _bank(len(bank_vecs))
        bank_vecs += [bank_vecs[0]] * (B - len(bank_vecs))
        bank = jnp.stack(bank_vecs)
        gather = idx
        if P != M:
            gather = np.concatenate([idx, np.full(P - M, idx[0])])
            sel = np.concatenate([sel, np.full(P - M, sel[0], np.int32)])
        self.launches += 1
        f_pred, s_soft = _feedback_launch(
            self._rep(bank), self._rep(sel), self._train_data, self._rep(gather), self._ops,
            spec=self.spec, num_classes=self.num_classes, task=self.task, block=block,
        )
        f_pred, s_soft = fetch((f_pred[:M], s_soft[:M]), "feedback")
        return np.asarray(f_pred), self.f_true[idx], np.asarray(s_soft)
