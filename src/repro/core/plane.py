"""Device-resident parameter plane: the server's hot matrix state.

EchoPFL's coordination layer is arithmetic over flattened parameter
vectors — L1 assignment distances (Eq. 1), mixed-rate center updates,
broadcast-gap norms, feedback probes. Keeping each of those vectors inside
a per-cluster pytree forces every arriving upload to re-flatten C pytrees
and re-stack them into a matrix (O(C * leaves) dispatches per upload).
Papaya-style async coordination only scales when that state is *already*
matrix-resident: one preallocated ``(capacity, dim)`` device buffer whose
rows are cluster centers, last-broadcast anchors, and per-client last
uploads, addressed through an explicit free-list.

Write-back is batched: row writes stage in a host-side dirty map (the
values are device arrays; only the row *bookkeeping* is host-side) and are
flushed into the buffer with a single scatter right before any batched
read (``rows``/``matrix``). Single-row reads are served straight from the
staging map, so ping-pong write/read of one row never touches the big
buffer. A batched producer of many rows (e.g. the client fleet refreshing
its evaluation-view rows after a broadcast) stages its whole ``(n, dim)``
batch with ONE :meth:`write_rows` call — the matrix is never sliced into
per-row values; flush applies staged matrices and then the per-row map,
later writes winning. Pytrees are materialized only at protocol
boundaries via the cached :class:`~repro.common.pytrees.FlattenSpec`
adapters.

The plane is a *generic* row store: the clustering layer keeps cluster
centers, broadcast anchors, and per-client last uploads in one plane, and
the client-fleet engine (:mod:`repro.fl.fleet`) keeps every simulated
device's model (plus its evaluation-view rows) in a second, independent
plane — separate instances are separate row namespaces, so fleet rows can
never collide with cluster rows.

Row-shard layout (fleet scale)
------------------------------
At the million-user north star the ``(capacity, dim)`` buffer outgrows one
accelerator's memory, so the plane optionally places it with a
``NamedSharding`` over a mesh (``launch.mesh.make_plane_mesh``): rows —
cluster centers, broadcast anchors, and per-client last uploads alike —
spread contiguously over the ``plane`` axis (device *i* owns rows
``[i*cap/S, (i+1)*cap/S)``), and the flat parameter dim may additionally
spread over a ``model`` axis when it divides. Capacity is rounded up to a
multiple of the row-shard count so every shard stays equal through
``_grow`` doublings, and the donated flush scatter preserves the placement
(re-pinned defensively if XLA ever drops it). Batched reads feed the
kernels in :mod:`repro.kernels.plane_sharded`, which run per-shard and
reduce across shards only where the protocol genuinely couples rows: an
``all_gather`` of per-shard distance vectors before an argmin, a one-hot
``psum`` to fetch the winning center row, and a ``psum`` of per-cluster
feedback segment sums. Everything per-row is bitwise-identical to the
single-device plane, so server trajectories do not depend on the mesh.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.common import tracing
from repro.common.pytrees import flatten_spec
from repro.common.tracing import span

PyTree = Any

# jitted vector helpers shared by the plane and the server hot path. The
# lerp is the canonical mixed-rate blend: ``t`` is static (folded exactly
# like the fused assign kernel folds its beta) and the two products are
# fenced apart (optimization_barrier) so XLA can never contract the
# mul-add into an FMA. Every path that blends a center — the assign
# kernel, this row lerp, the event-coalesced ingest scan — therefore emits
# the SAME two-op f32 expression regardless of surrounding fusion, which
# is what keeps batched and per-event server trajectories bitwise-equal.
import functools as _functools


@_functools.partial(jax.jit, static_argnames=("t",))
def lerp_vec(a, b, t):
    m1, m2 = jax.lax.optimization_barrier(((1.0 - t) * a, t * b))
    return m1 + m2


l1_vec = jax.jit(lambda a, b: jnp.sum(jnp.abs(a - b)))

# The flush scatter donates the buffer: without donation every row write-back
# would copy the whole (capacity, dim) plane, which scales with fleet size —
# exactly the O(capacity)-per-upload behavior the plane exists to avoid.
@_functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(buf, rows, vals):
    return buf.at[rows].set(vals)


@_functools.partial(jax.jit, donate_argnums=(0,))
def _set_row(buf, idx, vec):
    # single-row fast path: dynamic_update_slice lowers leaner than scatter
    return jax.lax.dynamic_update_slice_in_dim(buf, vec[None, :], idx, axis=0)


@jax.jit  # no donation: the output shape doubles, so aliasing is impossible
def _grow_buf(buf):
    return jnp.concatenate([buf, jnp.zeros_like(buf)], axis=0)


class ParameterPlane:
    """Preallocated ``(capacity, dim)`` row store for flat parameter vectors."""

    def __init__(
        self,
        template: PyTree,
        capacity: int = 32,
        dtype=jnp.float32,
        *,
        mesh: jax.sharding.Mesh | None = None,
        row_axis: str = "plane",
        dim_axis: str | None = "model",
    ):
        self.spec = flatten_spec(template, dtype)
        self.dim = self.spec.dim
        self.dtype = jnp.dtype(dtype)
        self.mesh = mesh
        self.row_axis = row_axis
        self.dim_axis = dim_axis
        self._row_shards = 1
        self._sharding: NamedSharding | None = None
        if mesh is not None and row_axis in mesh.axis_names:
            self._row_shards = mesh.shape[row_axis]
            dspec = (
                dim_axis
                if dim_axis is not None
                and dim_axis in mesh.axis_names
                and self.dim % mesh.shape[dim_axis] == 0
                else None
            )
            self._sharding = NamedSharding(mesh, PartitionSpec(row_axis, dspec))
            self._local_device = mesh.devices.flat[0]
            self._replicated = NamedSharding(mesh, PartitionSpec())
        capacity = max(1, int(capacity))
        # equal row shards, preserved through _grow doublings
        capacity = -(-capacity // self._row_shards) * self._row_shards
        self._buf = self._place(jnp.zeros((capacity, self.dim), self.dtype))
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._used: set[int] = set()
        self._dirty: dict[int, jax.Array] = {}
        # bulk-staged (row_ids, {row: position}, (n, dim) matrix) groups
        # from write_rows; applied in order at flush, before the per-row
        # dirty map. The position dict keeps single-row reads O(1) while a
        # fleet-sized batch is staged.
        self._bulk: list[tuple[list[int], dict[int, int], jax.Array]] = []
        # incrementally-patched gather cache: XLA's row gather is slow on
        # CPU, and the hot path (`assign`) requests the same center-row set
        # every upload while only the aggregated row changes — so a cached
        # view is patched with a 1-row scatter instead of re-gathered.
        # Keyed (row_ids, domain): "local" views feed single-device compute,
        # "mesh" views are mesh-replicated operands for sharded launches.
        self._views: dict[tuple, jax.Array] = {}
        self._view_stale: dict[tuple, set] = {}

    # ------------------------------------------------------------- placement
    def _place(self, buf: jax.Array) -> jax.Array:
        """Pin ``buf`` to the plane's row sharding (no-op when unsharded or
        already placed — XLA propagates the sharding through the donated
        scatters, so this is a correctness guard, not a per-flush copy)."""
        if self._sharding is None or (
            hasattr(buf, "sharding")
            and buf.sharding.is_equivalent_to(self._sharding, buf.ndim)
        ):
            return buf
        return jax.device_put(buf, self._sharding)

    def _localize(self, x: jax.Array) -> jax.Array:
        """Land a small read (one row, a row-set view) on a single device.

        A slice/gather of the sharded buffer comes back *committed to the
        whole mesh*, which turns every downstream consumer — the fused
        assign kernel on an 8-row center view, a gap norm — into a
        full-mesh SPMD dispatch. Small batches belong on one device (the
        same economics as ``mesh_min_rows``); the sharded kernel launches
        reshard their operands on entry regardless (ops._to_mesh)."""
        if self._sharding is None:
            return x
        sharding = getattr(x, "sharding", None)
        if sharding is not None and sharding.device_set == {self._local_device}:
            return x
        return jax.device_put(x, self._local_device)

    # ---------------------------------------------------------------- sizing
    @property
    def capacity(self) -> int:
        return self._buf.shape[0]

    @property
    def num_allocated(self) -> int:
        return len(self._used)

    def _grow(self) -> None:
        old_cap = self.capacity
        self._buf = self._place(_grow_buf(self._buf))
        self._free.extend(range(2 * old_cap - 1, old_cap - 1, -1))

    # ------------------------------------------------------------ allocation
    def alloc(self, value: PyTree | jax.Array | None = None) -> int:
        """Claim a row; ``value`` (vector or pytree) seeds it, else zeros.

        Zero-seeding matters: freed rows keep their old bytes in the buffer,
        and a reader of a recycled row must never see the previous tenant.
        """
        if not self._free:
            self._grow()
        row = self._free.pop()
        self._used.add(row)
        if value is None:
            self._dirty[row] = jnp.zeros((self.dim,), self.dtype)
        else:
            self.write(row, value)
        return row

    def alloc_many(self, n: int) -> list[int]:
        """Claim ``n`` zero-seeded rows with ONE staged write (a single
        ``write_rows`` bookkeeping entry instead of ``n`` per-row stagings)
        — the fleet-sized allocation path: the uplink codec claiming a
        per-client anchor + residual row for every simulated device."""
        if n <= 0:
            return []
        while len(self._free) < n:
            self._grow()
        rows = [self._free.pop() for _ in range(n)]
        self._used.update(rows)
        self.write_rows(rows, jnp.zeros((n, self.dim), self.dtype))
        return rows

    def free(self, row: int) -> None:
        if row not in self._used:
            raise KeyError(f"row {row} is not allocated")
        self._used.discard(row)
        self._dirty.pop(row, None)
        self._free.append(row)
        for key in [k for k in self._views if row in self._view_stale[k] or row in k[0]]:
            del self._views[key], self._view_stale[key]

    # ----------------------------------------------------------------- io
    def as_vec(self, value: PyTree | jax.Array) -> jax.Array:
        """Coerce a 1-D vector or a pytree to a plane-dtype row vector."""
        if isinstance(value, jax.Array) and value.ndim == 1 and value.dtype == self.dtype:
            return value  # hot path: rows handed back to the plane verbatim
        if not isinstance(value, (dict, list, tuple)) and getattr(value, "ndim", None) == 1:
            return jnp.asarray(value, self.dtype)
        return self.spec.flatten(value)

    def write(self, row: int, value: PyTree | jax.Array) -> None:
        """Stage a row write (flushed lazily before the next batched read)."""
        if row not in self._used:
            raise KeyError(f"row {row} is not allocated")
        vec = self.as_vec(value)
        if vec.shape != (self.dim,):
            raise ValueError(f"expected ({self.dim},) vector, got {vec.shape}")
        # normalize the staging domain: a value coming back from a sharded
        # kernel launch is mesh-committed, and mixing that with local-device
        # rows in later jitted arithmetic is a placement error
        self._dirty[row] = self._localize(vec)
        for key in self._views:
            if row in key[0]:
                self._view_stale[key].add(row)

    def write_rows(self, row_ids: Sequence[int], matrix: jax.Array) -> None:
        """Stage a batched write: ``matrix[i]`` lands in ``row_ids[i]``.

        The matrix is staged *whole* — one host-side bookkeeping entry, no
        per-row device slicing — which is what keeps a batched producer of
        n rows (the fleet's eval-view refresh after a broadcast, a
        fleet-scale reassign sweep) at O(1) staging cost instead of O(n).
        Later writes to the same rows (either per-row or a later
        ``write_rows``) win at flush time. Duplicate ids within one call
        are rejected: the scatter's resolution order for duplicates is
        unspecified, so the staged read and the flushed buffer could
        disagree."""
        ids = [int(r) for r in row_ids]
        if len(set(ids)) != len(ids):
            raise ValueError("write_rows: duplicate row ids in one batch")
        for r in ids:
            if r not in self._used:
                raise KeyError(f"row {r} is not allocated")
        with span("plane/stage", rows=len(ids)):
            matrix = jnp.asarray(matrix, self.dtype)
            if matrix.shape != (len(ids), self.dim):
                raise ValueError(f"expected ({len(ids)}, {self.dim}) matrix, got {matrix.shape}")
            # per-row staged values for these rows are older than this matrix
            for r in ids:
                self._dirty.pop(r, None)
            if self._bulk:
                # keep the staging list bounded at one live matrix: cached-view
                # reads patch in place without flushing, so without this an
                # eval-tick producer would grow _bulk by one matrix per tick
                self.flush()
            self._bulk.append((ids, {r: i for i, r in enumerate(ids)}, self._localize(matrix)))
            id_set = set(ids)
            for key in self._views:
                hit = id_set.intersection(key[0])
                if hit:
                    self._view_stale[key].update(hit)

    def flush(self) -> None:
        if not self._dirty and not self._bulk:
            return
        with span("plane/flush") as sp:
            if tracing.on():
                sp.set_metadata(rows=len(self._dirty) + sum(len(b[0]) for b in self._bulk))
            for ids, _, mat in self._bulk:
                self._buf = _scatter_rows(
                    self._buf, jnp.asarray(ids, jnp.int32), self._replicate(mat)
                )
            self._bulk = []
            if self._dirty:
                order = sorted(self._dirty)
                if len(order) == 1:
                    val = self._replicate(self._dirty[order[0]])
                    self._buf = _set_row(self._buf, jnp.int32(order[0]), val)
                else:
                    rows = jnp.asarray(order, jnp.int32)
                    vals = self._replicate(jnp.stack([self._dirty[r] for r in order]))
                    self._buf = _scatter_rows(self._buf, rows, vals)
                self._dirty.clear()
            self._buf = self._place(self._buf)

    def _replicate(self, v: jax.Array) -> jax.Array:
        """Move a staged value onto the mesh before it meets the sharded
        buffer in a jitted scatter (committed single-device operands and
        mesh-committed operands cannot share a jit)."""
        if self._sharding is None:
            return v
        return jax.device_put(v, self._replicated)

    def row(self, row: int) -> jax.Array:
        """Current ``(dim,)`` vector for one row (staged write wins)."""
        if row in self._dirty:
            return self._dirty[row]
        if row not in self._used:
            raise KeyError(f"row {row} is not allocated")
        for _, pos, mat in reversed(self._bulk):  # latest staged matrix wins
            p = pos.get(row)
            if p is not None:
                return self._localize(mat[p])
        return self._localize(self._buf[row])

    def _staged_rows(self, rs: list[int]) -> jax.Array:
        """(len(rs), dim) current values for ``rs``, preferring ONE gather
        from the live staged bulk matrix over per-row reads — this is what
        keeps a view patch after a fleet-wide ``write_rows`` at O(1)
        dispatches instead of one slice per stale row."""
        if self._bulk and not any(r in self._dirty for r in rs):
            _, pos, mat = self._bulk[-1]  # bounded: the only live matrix
            if all(r in pos for r in rs):
                sel = jnp.asarray([pos[r] for r in rs], jnp.int32)
                return self._localize(mat[sel])
        return jnp.stack([self.row(r) for r in rs])

    def _shard_rows(self, x: jax.Array) -> jax.Array:
        """Pin an ``(n, dim)`` row batch *sharded* over the plane's row axis
        (the operand form ``ops._to_mesh_rows`` passes through untouched)."""
        want = NamedSharding(self.mesh, PartitionSpec(self.row_axis, None))
        sharding = getattr(x, "sharding", None)
        if sharding is not None and sharding.is_equivalent_to(want, x.ndim):
            return x
        return jax.device_put(x, want)

    def take(self, row_ids: Sequence[int], *, on_mesh: bool | str = False) -> jax.Array:
        """Uncached ``(len(row_ids), dim)`` gather of the requested rows.

        Same placement semantics as :meth:`rows` (including the
        ``"shard"`` row-sharded form), but never touches the view cache: a
        caller gathering a *different* row set every call (a refine sweep's
        flagged members, a dissolve's victim uploads) must not evict the
        hot cached sets (the per-upload center matrix, the model-row bank,
        the eval-row bank)."""
        if len(row_ids) == 0:
            return jnp.zeros((0, self.dim), self.dtype)
        self.flush()
        view = self._buf[jnp.asarray(list(row_ids), jnp.int32)]
        if on_mesh == "shard" and self._sharding is not None:
            return self._shard_rows(view)
        return self._replicate(view) if on_mesh and self._sharding is not None else self._localize(view)

    def rows(self, row_ids: Sequence[int], *, on_mesh: bool | str = False) -> jax.Array:
        """Stacked ``(len(row_ids), dim)`` view of the requested rows.

        Repeat requests for the same row set (the per-upload center matrix)
        are served from a cached gather patched in place with the rows that
        changed since — O(changed_rows * dim), not O(len * dim). The
        returned array is a snapshot: valid until the same row set is
        requested again after a write.

        ``on_mesh`` asks for a mesh placement instead of the single local
        device — the operand forms the *sharded* kernel launches consume:
        ``True`` (or ``"replicate"``) replicates the view across the plane
        mesh (small operands: the center matrix every query row scores
        against); ``"shard"`` lands it sharded over the row axis (the
        fleet-scale row batch — a reassign/dissolve sweep over thousands of
        upload rows — which must never round-trip through one local device
        on exactly the path sharding exists to relieve). Either form is
        cached and patched exactly like the local view. Ignored (plain
        local view) when the plane is unsharded.
        """
        if len(row_ids) == 0:
            return jnp.zeros((0, self.dim), self.dtype)
        if self._sharding is None:
            on_mesh = False
        ids = tuple(row_ids)
        if on_mesh == "shard":
            key = (ids, "shard")
            place = self._replicate  # patch values enter like flush scatters
        elif on_mesh:
            key = (ids, "mesh")
            place = self._replicate
        else:
            key = (ids, "local")
            place = lambda v: v
        view = self._views.pop(key, None)  # pop + reinsert: move-to-end on hit
        if view is not None:
            stale = self._view_stale[key]
            if stale:
                if len(stale) == 1:
                    (r,) = stale
                    view = _set_row(view, jnp.int32(ids.index(r)), place(self.row(r)))
                else:
                    stale_list = list(stale)
                    pos = [ids.index(r) for r in stale_list]
                    vals = place(self._staged_rows(stale_list))
                    view = _scatter_rows(view, jnp.asarray(pos, jnp.int32), vals)
                if on_mesh == "shard":  # guard: the donated patch scatter
                    view = self._shard_rows(view)  # must not drop the placement
                stale.clear()
            self._views[key] = view
            return view
        self.flush()
        view = self._buf[jnp.asarray(list(ids), jnp.int32)]
        if on_mesh == "shard":
            view = self._shard_rows(view)
        else:
            view = self._replicate(view) if on_mesh else self._localize(view)
        if len(self._views) >= 4:  # tiny LRU cache: hot sets only. Insertion
            # order is recency order (hits reinsert), so the head is the
            # true LRU victim — a burst of cold reads can no longer evict
            # the hot per-upload center set just because it was cached first.
            oldest = next(iter(self._views))
            del self._views[oldest], self._view_stale[oldest]
        self._views[key] = view
        self._view_stale[key] = set()
        return view

    def matrix(self) -> jax.Array:
        """The full backing buffer (flushed). Never-allocated rows are
        zeros; *freed* rows keep their last tenant's bytes until realloc
        (``alloc`` zero-seeds, so ``row``/``rows`` of live rows never
        expose them). A snapshot view: valid until the next write-back
        donates the buffer."""
        self.flush()
        return self._buf

    # ------------------------------------------------------------ arithmetic
    def lerp_row(self, row: int, value: PyTree | jax.Array, t: float) -> None:
        """row <- (1 - t) * row + t * value (the async mixing step)."""
        self.write(row, lerp_vec(self.row(row), self.as_vec(value), t))

    def copy_row(self, src: int, dst: int) -> None:
        self.write(dst, self.row(src))

    def l1_rows(self, a: int, b: int) -> jax.Array:
        return l1_vec(self.row(a), self.row(b))

    # ------------------------------------------------------------- adapters
    def from_pytree(self, tree: PyTree) -> jax.Array:
        return self.spec.flatten(tree)

    def to_pytree(self, row: int) -> PyTree:
        return self.spec.unflatten(self.row(row))

    def vec_to_pytree(self, vec: jax.Array) -> PyTree:
        return self.spec.unflatten(vec)
