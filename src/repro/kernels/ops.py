"""Public jit'd entry points for the Pallas kernels.

``REPRO_KERNELS`` (read per call) picks the backend: ``auto`` (default)
compiles the Pallas kernels on a TPU and runs the jnp oracles in
``kernels/ref.py`` on any other backend; ``pallas`` forces the kernel bodies
(interpreted on the CPU backend, compiled elsewhere); ``ref`` forces the
oracles. The protocol layer calls only these wrappers, so swapping the
backend never touches coordination code.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import plane_sharded, ref
from repro.kernels.assign_lerp import assign_and_lerp as _assign_lerp_kernel
from repro.kernels.assign_lerp import select_lerp as _select_lerp_kernel
from repro.kernels.chi2_feedback import chi2_feedback as _chi2_kernel
from repro.kernels.chi2_feedback import chi2_feedback_segmented as _chi2_seg_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.l1_distance import l1_distance as _l1_kernel
from repro.kernels.l1_pairwise import l1_distance_pairwise as _l1_pairwise_kernel
from repro.kernels.merge_attention import merge_attention as _merge_kernel


def _use_pallas() -> bool:
    force = os.environ.get("REPRO_KERNELS") or "auto"
    if force not in ("auto", "pallas", "ref"):
        raise ValueError(f"REPRO_KERNELS={force!r}: expected auto, pallas or ref")
    if force == "auto":
        return jax.default_backend() == "tpu"
    return force == "pallas"


def _interpret() -> bool:
    """Pallas kernels run interpreted on the CPU backend and nowhere else."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window", "softcap", "q_pos0"))
def flash_attention(q, k, v, *, causal=True, scale=None, window=None, softcap=None, q_pos0=0):
    if _use_pallas():
        return _flash_kernel(
            q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
            q_pos0=q_pos0, interpret=_interpret(),
        )
    return ref.flash_attention_ref(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap, q_pos0=q_pos0
    )


# ---------------------------------------------------------------------------
# Trainable attention: flash forward + flash backward kernels via custom_vjp.
# This is what the model's train/prefill path calls — it is the difference
# between O(S^2) attention HBM traffic (materialized score matrices, the
# REPRO_KERNELS=ref oracle) and the O(S^2 * d / block) streaming traffic of
# the fused kernels.
# ---------------------------------------------------------------------------
from repro.kernels.flash_attention import flash_attention_with_lse as _flash_fwd_lse
from repro.kernels.flash_attention_bwd import flash_attention_bwd as _flash_bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention_trainable(q, k, v, causal, scale, window, softcap, q_pos0, interpret):
    o, _ = _flash_fwd_lse(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
        q_pos0=q_pos0, interpret=interpret,
    )
    return o


def _attention_fwd(q, k, v, causal, scale, window, softcap, q_pos0, interpret):
    o, lse = _flash_fwd_lse(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
        q_pos0=q_pos0, interpret=interpret,
    )
    return o, (q, k, v, o, lse)


def _attention_bwd(causal, scale, window, softcap, q_pos0, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, do, causal=causal, scale=scale, window=window,
        softcap=softcap, q_pos0=q_pos0, interpret=interpret,
    )
    return dq, dk, dv


_attention_trainable.defvjp(_attention_fwd, _attention_bwd)


def _local_attention(q, k, v, *, causal, scale, window, softcap, q_pos0):
    """Per-device attention on local (B, H, S, hd) shards.

    REPRO_ATTN_COST_PROXY=1 (set by the dry-run) lowers the AD-able jnp
    reference instead of the interpret-mode kernels: interpret lowering
    copies full loop-carried arrays per grid step (a CPU emulation artifact
    a Mosaic kernel does not have), which poisons byte accounting. The cost
    model then filters the reference's S^2 tensors and substitutes the
    kernels' analytic streaming traffic (hlo_cost.skip_trailing +
    dryrun.flash_attention_analytic_bytes).

    Backend dispatch follows the documented ``REPRO_KERNELS`` contract
    (same rule as ``flash_attention`` above): ``auto`` lowers the kernels
    only on TPU and the AD-able jnp oracle elsewhere — interpret-mode
    execution is a per-grid-step interpreter loop, ~100x slower than the
    oracle under a wide vmap (the LM fleet's cohort launches), and is
    reserved for the explicit ``pallas`` CI parity sweeps."""
    if os.environ.get("REPRO_ATTN_COST_PROXY") == "1" or not _use_pallas():
        return ref.flash_attention_ref(
            q, k, v, causal=causal, scale=scale, window=window,
            softcap=softcap, q_pos0=q_pos0,
        )
    return _attention_trainable(
        q, k, v, causal, scale, window, softcap, q_pos0, _interpret()
    )


def attention(q, k, v, *, causal=True, scale=None, window=None, softcap=None, q_pos0=0):
    """Training/prefill attention entry point (B, H, Sq, hd) x (B, KV, Sk, *).

    Under a registered mesh (repro.models.dist) the computation runs inside
    shard_map on per-device local shapes: batch over ("pod","data"), heads
    over "model" when divisible. GQA with fewer KV heads than the TP width
    keeps K/V replicated and slices the per-rank KV group inside the shard —
    the standard TP layout for GQA.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models import dist

    mesh = dist.current_mesh()
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    kw = dict(causal=causal, scale=scale, window=window, softcap=softcap, q_pos0=q_pos0)
    if mesh is None or mesh.devices.size == 1:
        return _local_attention(q, k, v, **kw)

    baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp = 1
    for a in baxes:
        dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    b_ax = baxes if B % dp == 0 else None
    h_sharded = H % tp == 0 and tp > 1
    kv_sharded = h_sharded and KV % tp == 0
    h_ax = "model" if h_sharded else None
    kv_ax = "model" if kv_sharded else None

    G = H // KV
    h_local = H // tp if h_sharded else H

    def body(ql, kl, vl):
        if h_sharded and not kv_sharded:
            # slice this rank's KV group out of the replicated K/V
            rank = jax.lax.axis_index("model")
            kv_need = max(1, h_local // G)
            kv0 = rank * h_local // G
            kl_ = jax.lax.dynamic_slice_in_dim(kl, kv0, kv_need, axis=1)
            vl_ = jax.lax.dynamic_slice_in_dim(vl, kv0, kv_need, axis=1)
        else:
            kl_, vl_ = kl, vl
        return _local_attention(ql, kl_, vl_, **kw)

    from jax import shard_map

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(b_ax, h_ax, None, None), P(b_ax, kv_ax, None, None),
                  P(b_ax, kv_ax, None, None)),
        out_specs=P(b_ax, h_ax, None, None),
        check_vma=False,
    )(q, k, v)


@jax.jit
def l1_distance(u, centers):
    if _use_pallas():
        return _l1_kernel(u, centers, interpret=_interpret())
    return ref.l1_distance_ref(u, centers)


@jax.jit
def merge_attention(v_main, v_aux, v_trained):
    if _use_pallas():
        return _merge_kernel(v_main, v_aux, v_trained, interpret=_interpret())
    return ref.merge_attention_ref(v_main, v_aux, v_trained)[0]


def _chi2_local(f_pred, f_true, s_soft):
    if _use_pallas():
        return _chi2_kernel(f_pred, f_true, s_soft, interpret=_interpret())
    return ref.chi2_feedback_ref(f_pred, f_true, s_soft)


@jax.jit
def _chi2_single(f_pred, f_true, s_soft):
    return _chi2_local(f_pred, f_true, s_soft)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "dim_axis"))
def _chi2_mesh(f_pred, f_true, s_soft, mesh, axis, dim_axis=None):
    return plane_sharded.chi2_rows_sharded(
        f_pred, f_true, s_soft, mesh, axis, _chi2_local, dim_axis=dim_axis
    )


def chi2_feedback(f_pred, f_true, s_soft, *, mesh=None, axis="plane", dim_axis="model"):
    """Per-row Eq. 2/3 feedback statistic, (M, J) -> (M,) in one launch.

    With a plane mesh, the M probe rows shard over ``axis`` — and over the
    model axis too when one is active (the feedback operands have no model
    dim, so it contributes row-parallelism) — and every shard scores only
    its rows (per-row arithmetic is shard-local, so scores are
    bitwise-identical to the single-device launch). This is the
    dissolve/expand probe path: it goes sharded only when the flagged-pair
    count crosses the plane's ``mesh_min_rows`` threshold."""
    ms = _model_axis_size(mesh, dim_axis) if mesh is not None else 1
    if _mesh_active(mesh, axis) or ms > 1:
        M = f_pred.shape[0]
        da = dim_axis if ms > 1 else None
        row_axes = (axis, da) if da is not None else (axis,)
        f_pred = _to_mesh_rows(mesh, axis, jnp.asarray(f_pred), row_axes=row_axes)
        f_true = _to_mesh_rows(mesh, axis, jnp.asarray(f_true), fill=1, row_axes=row_axes)
        s_soft = _to_mesh_rows(mesh, axis, jnp.asarray(s_soft), row_axes=row_axes)
        return _chi2_mesh(f_pred, f_true, s_soft, mesh=mesh, axis=axis, dim_axis=da)[:M]
    return _chi2_single(f_pred, f_true, s_soft)


# ---------------------------------------------------------------------------
# Batched plane kernels. Each public wrapper takes an optional plane mesh:
# with ``mesh=None`` (default) the single-device path runs unchanged; with a
# row-sharded mesh the same kernel bodies run per-shard inside shard_map
# (see kernels/plane_sharded.py for the reduction points). Mesh and axis are
# static jit arguments, so each (mesh, shape) pair compiles once.
# ---------------------------------------------------------------------------


def _mesh_active(mesh, axis: str) -> bool:
    return mesh is not None and axis in mesh.axis_names and mesh.shape[axis] > 1


def _model_compute_on() -> bool:
    """The model-axis compute knob (REPRO_PLANE_MODEL_COMPUTE): ``on`` (the
    default) lets an R×M plane mesh shard kernel *compute* over the flat
    parameter dim; ``off`` reverts to replicating operands over the model
    axis (storage may still shard — the PR-2 behavior). Read per call so
    tests can flip it without reimporting."""
    return os.environ.get("REPRO_PLANE_MODEL_COMPUTE", "on").lower() not in (
        "off", "0", "none", "false"
    )


def _model_axis_size(mesh, dim_axis) -> int:
    """Model-axis extent usable for compute (1 when absent/disabled)."""
    if mesh is None or dim_axis is None or not _model_compute_on():
        return 1
    if dim_axis not in mesh.axis_names:
        return 1
    return int(mesh.shape[dim_axis])


def _dim_shards(mesh, dim_axis, dim: int) -> int:
    """Shard count for splitting a flat parameter dim over the model axis:
    the axis extent when it divides ``dim``, else 1 (fall back to
    replicated operands, mirroring the plane's storage rule)."""
    m = _model_axis_size(mesh, dim_axis)
    return m if m > 1 and dim % m == 0 else 1


def _to_mesh(mesh, *arrays):
    """Replicate *small, genuinely replicated* operands (the arriving upload
    vector, the center matrix every query row scores against) onto the mesh
    before a sharded launch. The plane serves small reads committed to a
    single device (plane._localize), and a jit spanning the whole mesh
    rejects single-device-committed inputs rather than resharding them — so
    the dispatch layer moves them here. Arrays already living on the mesh's
    device set pass through untouched."""
    from jax.sharding import NamedSharding, PartitionSpec

    devices = frozenset(mesh.devices.flat)
    rep = NamedSharding(mesh, PartitionSpec())
    out = []
    for x in arrays:
        sharding = getattr(x, "sharding", None)
        if sharding is not None and sharding.device_set == devices:
            out.append(x)
        else:
            out.append(jax.device_put(x, rep))
    return out


def _to_mesh_rows(mesh, axis, x, fill=0, *, row_axes=None, dim_axis=None):
    """Place a row-batched operand *sharded* over ``axis`` (rows padded up
    to the shard count first). The fleet-scale operand — an (M, dim) upload
    matrix, (M, J) feedback rows — must never be materialized whole on
    every device; replicate-then-reshard would cost shard_count x the
    sharded footprint on exactly the path sharding exists to relieve.

    ``row_axes`` spreads the rows over several mesh axes jointly (the chi2
    kernels recruit the model axis for row-parallelism); ``dim_axis``
    additionally shards the trailing dim (the L1 kernels' partial-sum
    operands — the caller guarantees divisibility)."""
    from jax.sharding import NamedSharding, PartitionSpec

    axes = tuple(row_axes) if row_axes is not None else (axis,)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    rows = x.shape[0]
    rows_p = -(-rows // shards) * shards
    if rows_p != rows:
        x = jnp.pad(
            jnp.asarray(x),
            ((0, rows_p - rows),) + ((0, 0),) * (x.ndim - 1),
            constant_values=fill,
        )
    trailing = [None] * (x.ndim - 1)
    if dim_axis is not None and trailing:
        trailing[-1] = dim_axis
    rows_spec = axes[0] if len(axes) == 1 else axes
    want = NamedSharding(mesh, PartitionSpec(rows_spec, *trailing))
    sharding = getattr(x, "sharding", None)
    if sharding is not None and sharding.is_equivalent_to(want, x.ndim):
        return x
    return jax.device_put(x, want)


def _to_mesh_dim(mesh, dim_axis, *arrays):
    """Place small operands with only the trailing dim sharded over the
    model axis (replicated over rows): the arriving upload vector and the
    center matrix of a dim-sharded launch. Arrays already laid out that way
    (a plane ``take`` off a dim-sharded row store) pass through."""
    from jax.sharding import NamedSharding, PartitionSpec

    out = []
    for x in arrays:
        want = NamedSharding(
            mesh, PartitionSpec(*(None,) * (x.ndim - 1), dim_axis)
        )
        sharding = getattr(x, "sharding", None)
        if sharding is not None and sharding.is_equivalent_to(want, x.ndim):
            out.append(x)
        else:
            out.append(jax.device_put(x, want))
    return out


def _l1_pairwise_local(xs, centers):
    if _use_pallas():
        return _l1_pairwise_kernel(xs, centers, interpret=_interpret())
    return ref.l1_distance_pairwise_ref(xs, centers)


def _l1_local(u, centers):
    if _use_pallas():
        return _l1_kernel(u, centers, interpret=_interpret())
    return ref.l1_distance_ref(u, centers)


def _chi2_seg_local(f_pred, f_true, s_soft, onehot):
    if _use_pallas():
        return _chi2_seg_kernel(f_pred, f_true, s_soft, onehot, interpret=_interpret())
    return ref.chi2_feedback_segmented_ref(f_pred, f_true, s_soft, onehot)


@jax.jit
def _l1_pairwise_single(xs, centers):
    return _l1_pairwise_local(xs, centers)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "dim_axis"))
def _l1_pairwise_mesh(xs, centers, mesh, axis, dim_axis=None):
    return plane_sharded.l1_pairwise_sharded(
        xs, centers, mesh, axis, _l1_pairwise_local, dim_axis=dim_axis
    )


def l1_distance_pairwise(xs, centers, *, mesh=None, axis="plane", dim_axis="model"):
    """(M, N) x (C, N) -> (M, C) L1 matrix in one launch (plane hot path).

    With a plane mesh, the M query rows shard over ``axis`` and each shard
    scores only its rows (identical per-row arithmetic). With a model axis
    whose extent divides N, the flat dim shards too: each shard scores its
    dim chunk and a psum over ``dim_axis`` completes the matrix."""
    ds = _dim_shards(mesh, dim_axis, xs.shape[-1]) if mesh is not None else 1
    if _mesh_active(mesh, axis) or ds > 1:
        M = xs.shape[0]
        da = dim_axis if ds > 1 else None
        xs = _to_mesh_rows(mesh, axis, xs, dim_axis=da)
        if da is not None:
            (centers,) = _to_mesh_dim(mesh, da, centers)
        else:
            (centers,) = _to_mesh(mesh, centers)
        return _l1_pairwise_mesh(xs, centers, mesh=mesh, axis=axis, dim_axis=da)[:M]
    return _l1_pairwise_single(xs, centers)


@functools.partial(jax.jit, static_argnames=("beta",))
def _assign_lerp_single(u, centers, beta):
    if _use_pallas():
        return _assign_lerp_kernel(u, centers, beta, interpret=_interpret())
    return ref.assign_and_lerp_ref(u, centers, beta)


@functools.partial(jax.jit, static_argnames=("beta",))
def _select_lerp_single(row, u, beta):
    return _select_lerp_kernel(row[None], u, jnp.int32(0), beta, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("beta",))
def _lerp_products(row, u, beta):
    return (1.0 - beta) * row.astype(jnp.float32), beta * u.astype(jnp.float32)


_add = jax.jit(jnp.add)


def _lerp_row(row, u, beta):
    """The blend of ``_assign_lerp_single`` for one given row: the Pallas
    select-lerp kernel, or the oracle's two products and their sum as
    separate programs. XLA drops optimization barriers on some paths (the
    SPMD partitioner, small programs), and a fused multiply-add rounds
    once, an ulp off the two-op blend."""
    if _use_pallas():
        return _select_lerp_single(row, u, beta)
    return _add(*_lerp_products(row, u, beta))


@functools.partial(jax.jit, static_argnames=("valid_rows", "mesh", "axis", "dim_axis"))
def _assign_mesh(u, centers, valid_rows, mesh, axis, dim_axis=None):
    return plane_sharded.assign_sharded(
        u, centers, mesh, axis, _l1_local, valid_rows=valid_rows, dim_axis=dim_axis,
    )


def assign_and_lerp(u, centers, beta, *, mesh=None, axis="plane", dim_axis="model"):
    """Fused Eq. 1 argmin + mixed-rate center blend: (dists, idx, blended).

    With a plane mesh, the C center rows shard over ``axis``; distances
    all_gather, the argmin replicates, and the winning row is fetched with
    a one-hot psum — the full center matrix never moves — and blended on
    the mesh's first device. With a model axis whose extent divides N, the dim
    shards too: per-shard partial L1 sums psum into the distances."""
    ds = _dim_shards(mesh, dim_axis, u.shape[-1]) if mesh is not None else 1
    if _mesh_active(mesh, axis) or ds > 1:
        C = centers.shape[0]
        da = dim_axis if ds > 1 else None
        centers = _to_mesh_rows(mesh, axis, centers, dim_axis=da)
        if da is not None:
            (u_mesh,) = _to_mesh_dim(mesh, da, u)
        else:
            (u_mesh,) = _to_mesh(mesh, u)
        dists, idx, row = _assign_mesh(
            u_mesh, centers, valid_rows=C, mesh=mesh, axis=axis, dim_axis=da
        )
        # the blend runs outside the SPMD program (see
        # plane_sharded.assign_sharded), bitwise the unsharded blend, on the
        # device where the plane keeps its small reads
        dev = mesh.devices.flat[0]
        return dists, idx, _lerp_row(jax.device_put(row, dev), jax.device_put(u, dev), beta)
    return _assign_lerp_single(u, centers, beta)


@functools.partial(jax.jit, static_argnames=("beta", "switch_margin", "with_stats"))
def _ingest_chain_jit(U, centers, bcast, num_centers, prev_idx, forced_idx, valid, beta, switch_margin,
                      with_stats=False):
    C = centers.shape[0]
    # padded center rows (C is pow2-padded so the jit cache does not grow a
    # new entry every time a cluster expands or merges) can never win: the
    # per-row distances are computed as usual, then masked to +inf. The
    # real rows' distances are untouched, so decisions stay bitwise.
    row_valid = jnp.arange(C) < num_centers

    def step(cmat, inp):
        u, prev, forced, ok = inp
        # Optimization barriers fence each sub-expression into the same
        # isolated form the per-event path lowers as its own jit (the
        # assign kernel, plane.lerp_vec, plane.l1_vec): without them XLA
        # fuses/contracts across the scan body and the blends and L1 stats
        # drift from the sequential trajectory by an ulp — enough to flip a
        # downstream RNN broadcast decision. Bitwise parity is the contract.
        # _l1_local: the exact Eq. 1 arithmetic of the active backend, the
        # same dispatch rule assign_and_lerp feeds its argmin
        u, cmat_in = jax.lax.optimization_barrier((u, cmat))
        dists = jax.lax.optimization_barrier(_l1_local(u, cmat_in))
        dists = jnp.where(row_valid, dists, jnp.float32(jnp.inf))
        amin = jnp.argmin(dists).astype(jnp.int32)  # first-index ties, like np.argmin
        has_prev = prev >= 0
        d_prev = jnp.where(has_prev, dists[jnp.clip(prev, 0, C - 1)], jnp.float32(jnp.inf))
        veto = has_prev & (prev != amin) & (dists[amin] > (1.0 - switch_margin) * d_prev)
        cid = jnp.where(forced >= 0, forced, jnp.where(veto, prev, amin)).astype(jnp.int32)
        c_old = jax.lax.optimization_barrier(cmat_in[cid])
        # the canonical blend: every per-event flavor (the fused assign
        # kernel's winner blend, plane.lerp_row's veto/forced lerp) emits
        # this exact folded-beta, fenced two-op expression, so ONE form here
        # covers them all — no select, whose operands XLA is free to
        # re-derive with contracted arithmetic when it sinks the pick into
        # the surrounding fusion
        m1, m2 = jax.lax.optimization_barrier(
            ((1.0 - beta) * c_old, beta * u.astype(jnp.float32))
        )
        c_new = jax.lax.optimization_barrier(m1 + m2)
        b_row = jax.lax.optimization_barrier(bcast[cid])
        change = jnp.sum(jnp.abs(c_new - c_old))
        gap_before = jnp.sum(jnp.abs(c_old - b_row))
        gap_after = jnp.sum(jnp.abs(c_new - b_row))
        cmat = jnp.where(ok, cmat.at[cid].set(c_new), cmat)
        out = (cid, c_new, change, gap_before, gap_after)
        if with_stats:
            # guard telemetry riding the same launch/sync: the post-blend
            # center L1 norm (NaN/Inf propagate through the sum, so one
            # scalar covers both the finite gate and the blowup bound)
            out = out + (jnp.sum(jnp.abs(c_new)),)
        return cmat, out

    _, outs = jax.lax.scan(step, centers.astype(jnp.float32), (U, prev_idx, forced_idx, valid))
    return outs


def ingest_chain(U, centers, bcast, prev_idx, forced_idx, valid, *, beta,
                 switch_margin=0.1, num_centers=None, with_stats=False):
    """Sequential-equivalent batched server ingest: one launch scanning the
    fused assign+lerp over a window of concurrently-arrived uploads.

    Per step ``j`` (in event order) against the LIVE center matrix — each
    step sees every earlier step's blend, exactly like N sequential
    ``handle_upload`` calls:

      * Eq. 1 distances + argmin via the backend assign kernel,
      * host-identical hysteresis (``switch_margin``) with per-upload
        ``prev_idx`` (-1 = first upload) and ``forced_idx`` (>= 0 pins a
        partial-finetune member to its cluster, skipping the argmin),
      * the mixed-rate blend written into the carried center matrix,
      * the predictor statistics the per-event path reads back per upload:
        L1 change of the blended center and its gap to the broadcast
        anchor before/after (``bcast`` is the window-start anchor matrix;
        the caller recomputes the gaps of uploads that land after an
        intra-window broadcast, which moves the anchor).

    Returns per-step ``(cid (S,), blended (S, dim), change (S,),
    gap_before (S,), gap_after (S,))`` — plus the post-blend center L1
    norm ``cnorm (S,)`` when ``with_stats`` (the ingest guard's late
    NaN/blowup detector, riding the launch and sync the caller already
    pays; ``with_stats=False`` compiles the exact pre-guard program).
    Rows where ``valid`` is False leave
    the carried centers untouched and their outputs are ignored. ``U`` must
    be pre-padded by the caller (pad rows invalid), and ``centers``/
    ``bcast`` may carry zero-padding rows above ``num_centers`` (a traced
    count, masked to +inf distance) — so the jit cache stays O(log window)
    x O(log clusters)."""
    C = centers.shape[0]
    return _ingest_chain_jit(
        jnp.asarray(U), centers, bcast,
        jnp.int32(C if num_centers is None else num_centers),
        jnp.asarray(prev_idx, jnp.int32), jnp.asarray(forced_idx, jnp.int32),
        jnp.asarray(valid, jnp.bool_), beta, switch_margin, with_stats,
    )


@jax.jit
def _predictor_chain_jit(params, pre, post, lab_table, fb_table, learn_gate,
                         decide_gate, fb_gate, start, lr):
    from repro.core.broadcast import rnn_chain_step

    S = lab_table.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)

    def step(carry, inp):
        p, fire = carry
        pre_j, post_j, lab_row, fb_row, lg_j, dg_j, fg_j, pos_j = inp
        new_p, want_rnn = rnn_chain_step(
            p, pre_j, post_j, lab_row[fire], lg_j, dg_j, lr, start
        )
        want = jnp.where(fg_j, fb_row[fire], want_rnn)
        fire = jnp.where(want, pos_j + 1, fire)
        return (new_p, fire), want

    (final, _), wants = jax.lax.scan(
        step, (params, jnp.int32(0)),
        (pre, post, lab_table, fb_table, learn_gate, decide_gate, fb_gate, pos),
    )
    return final, wants


def predictor_chain(params, pre, post, lab_table, fb_table, learn_gate,
                    decide_gate, fb_gate, start, lr):
    """Fused broadcast-predictor chain: every learn/decide step one cluster
    accumulates over a coalesced window in ONE launch, instead of two
    dispatches plus a blocking sync per upload.

    One ``lax.scan`` walks the cluster's steps in chronological order with
    its RNN tree as carry; each step runs the cond-gated SGD on the
    pre-observe record window then the cond-gated broadcast decision on the
    post-observe window. The carry is the single NATIVE-shape tree — this
    is load-bearing for the bitwise contract with the per-upload
    `_rnn_sgd`/`_rnn_want` path. Cross-cluster batching was tried twice and
    both forms break it or don't pay:

      * one B-stacked tree with a gathered (h, h) slice per step makes XLA
        lower the dots against sliced operands with a different
        accumulation order, an ulp off the serial graph (vmapping clusters
        drifts the same way);
      * a tuple-of-B-trees carry with ``lax.switch`` per step IS bitwise,
        but both its compile time and its per-step runtime grow with the
        branch count — at fleet scale (many clusters per window) it lost
        more than the saved dispatches, and every distinct cluster count
        recompiled.

    Cluster chains are fully independent (each step touches only its own
    cluster's tree), so per-cluster launches lose nothing semantically: the
    caller fires one launch per touched cluster and syncs all their
    decisions with one blocking gather per window.

    The chain resolves the label/decision circularity IN-SCAN rather than
    by host fixpoint iteration: a step's Eq. 4 label and a cold-start
    fallback decision depend on the cluster's broadcast anchor, and
    within one window the anchor can only be the pre-window anchor or the
    blended vector of an earlier fired step of the SAME chain. The caller
    enumerates those candidates and precomputes, per step, a boolean row
    over "last fired chain position" (host float64 arithmetic, identical
    to the serial rules — no float compare happens on device). The scan
    carries the fired-position index alongside the RNN tree: each step
    gathers its label from ``lab_table[fire]``, a fallback step gathers
    its decision from ``fb_table[fire]``, and a fired want advances
    ``fire`` to its own position. Every step therefore executes exactly
    once per window, with no relaunches.

    Ragged Top-K windows (predictor ``k`` varies with cluster size) are
    front-padded to ``K``; ``start`` (scalar: K minus the real window
    length, fixed for the cluster) marks where the real window begins and
    the RNN holds its hidden state at zero before it, so valid steps see
    exactly the serial operands.

    Shapes: params is one RNN pytree; pre/post (S, K, 1); lab_table (S,
    S+1) int32 and fb_table (S, S+1) bool, column 0 meaning "pre-window
    anchor" and column q+1 meaning "step q fired last"; the three gates
    are (S,) bool (fb_gate marks cold-start fallback steps, which skip
    both RNN bodies); start/lr scalars. Callers pow2-pad S and K (pad
    steps have all gates False — an identity rewrite that skips both RNN
    bodies via the step's conds). Returns (final params tree, wants (S,)
    bool) covering RNN and fallback decisions alike.

    Operands stay host-side numpy right up to the jit boundary: the
    launch is fired once per cluster per window, and eager
    ``jnp.asarray`` staging cost more dispatch time than the chain saved.
    The numpy scalars keep strong dtypes (a weak python float for ``lr``
    would change promotion inside the SGD and break the bitwise match)."""
    return _predictor_chain_jit(
        params, np.asarray(pre, np.float32), np.asarray(post, np.float32),
        np.asarray(lab_table, np.int32), np.asarray(fb_table, np.bool_),
        np.asarray(learn_gate, np.bool_), np.asarray(decide_gate, np.bool_),
        np.asarray(fb_gate, np.bool_), np.int32(start), np.float32(lr),
    )


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _chi2_all_single(f_pred, f_true, s_soft, seg_ids, num_segments):
    onehot = (seg_ids[:, None] == jnp.arange(num_segments)[None, :]).astype(jnp.float32)
    return _chi2_seg_local(f_pred, f_true, s_soft, onehot)


@functools.partial(jax.jit, static_argnames=("num_segments", "mesh", "axis", "dim_axis"))
def _chi2_all_mesh(f_pred, f_true, s_soft, seg_ids, num_segments, mesh, axis, dim_axis=None):
    onehot = (seg_ids[:, None] == jnp.arange(num_segments)[None, :]).astype(jnp.float32)
    return plane_sharded.chi2_all_sharded(
        f_pred, f_true, s_soft, onehot, mesh, axis, _chi2_seg_local, dim_axis=dim_axis
    )


def chi2_feedback_all(f_pred, f_true, s_soft, seg_ids, num_segments, *, mesh=None,
                      axis="plane", dim_axis="model"):
    """Cluster-segmented feedback: every member of every cluster in one
    launch. ``seg_ids`` maps each row to its cluster slot in [0,
    num_segments); returns (g (M,), seg_sum (num_segments,)). With a plane
    mesh, member rows shard over ``axis`` — plus the model axis when one is
    active (row-parallelism; per-member g stays bitwise) — and segment sums
    psum over every sharded axis."""
    ms = _model_axis_size(mesh, dim_axis) if mesh is not None else 1
    if _mesh_active(mesh, axis) or ms > 1:
        M = f_pred.shape[0]
        da = dim_axis if ms > 1 else None
        row_axes = (axis, da) if da is not None else (axis,)
        f_pred = _to_mesh_rows(mesh, axis, f_pred, row_axes=row_axes)
        f_true = _to_mesh_rows(mesh, axis, f_true, row_axes=row_axes)
        s_soft = _to_mesh_rows(mesh, axis, s_soft, row_axes=row_axes)
        # padded members get segment -1: a one-hot row of zeros, so they
        # never contribute to any cluster's sum
        seg_ids = _to_mesh_rows(
            mesh, axis, jnp.asarray(seg_ids, jnp.int32), fill=-1, row_axes=row_axes
        )
        g, seg = _chi2_all_mesh(
            f_pred, f_true, s_soft, seg_ids, num_segments, mesh=mesh, axis=axis,
            dim_axis=da,
        )
        return g[:M], seg
    return _chi2_all_single(f_pred, f_true, s_soft, seg_ids, num_segments)
