"""Row-sharded execution of the batched plane kernels (fleet-scale path).

When the parameter plane shards its ``(capacity, dim)`` row store over a
``plane`` mesh axis, the batched coordination kernels must consume sharded
operands without gathering fleet state onto one device. Each wrapper here
runs the single-device kernel body (Pallas on TPU, jnp oracle elsewhere)
inside ``shard_map`` on the *local* row shard and stitches the global
answer with one collective:

  * ``l1_pairwise_sharded`` — query rows shard over ``plane``; every shard
    scores its rows against the (replicated) centers. No reduction: the
    (M, C) output is row-sharded and reassembles on exit.
  * ``assign_sharded`` — center rows shard over ``plane``; local
    distance vectors ``all_gather`` into the full (C,) vector, the argmin
    is computed redundantly on every shard, and the winning center row is
    recovered with a one-hot ``psum`` (only its owner contributes), so the
    blend (run by the caller on one device) never moves the whole center
    matrix.
  * ``chi2_all_sharded`` — member rows shard over ``plane``; per-cluster
    segment sums are partial per shard and ``psum`` into the global sums.
  * ``chi2_rows_sharded`` — the dissolve/expand probe matrix: rows shard
    over ``plane`` with no reduction (per-row scores reassemble on exit).

Per-row arithmetic (distances, feedback statistics, the blended row) is
bitwise-identical to the single-device kernels — each row's reduction runs
unchanged on whichever shard owns it — so server *decisions* (assignments,
merges, broadcasts) are trajectory-identical under sharding. Only the
cross-shard ``psum`` of segment sums may differ from sequential
accumulation in the last ulp, and that value feeds reporting, not control
flow.

Padding and placement are owned by the dispatch layer (``ops._to_mesh_rows``
pads row counts up to a shard multiple and device_puts the operand with the
row sharding; ``ops._to_mesh`` replicates the small operands): the wrappers
here assume shard-divisible inputs and handle only the *masking* —
padded center rows go to ``+inf`` distance before any argmin
(``valid_rows``), padded member rows carry an all-zero segment one-hot
(segment id -1) — while the dispatch slices padded query rows off the
output.

Meshes with an extra ``model`` axis additionally shard the *compute* over
the flat parameter dim (``dim_axis``), so a row wider than one device
never materializes whole anywhere:

  * the L1 kernels run the single-device kernel body on each shard's dim
    chunk — a chunk's L1 IS the partial sum over those coordinates — and
    one ``psum`` over ``dim_axis`` stitches the full per-row distances
    (last-ulp vs the single-device flat reduction; the R×M subprocess
    trajectory harness in tests/test_model_axis_plane.py pins that the
    server's *decisions* and blended centers stay identical);
  * the winning row of the assign comes back dim-sharded; the caller's
    single-device blend is bitwise the single-device plane's;
  * the chi2 kernels spread their member/probe *rows* over both axes
    (the feedback operands have no model dim — per-row arithmetic stays
    shard-local and bitwise; segment sums psum over both axes).

The dispatch layer only passes ``dim_axis`` when the model axis is real
(present, >1 shards, knob on) and — for the L1 kernels — the flat dim is
shard-divisible; otherwise these wrappers replicate over it exactly as
before (the plane may still *store* ``dim`` sharded; shard_map reshards
on entry).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def l1_pairwise_sharded(
    xs: jax.Array,  # (M_padded, N) query rows, shard-divisible
    centers: jax.Array,  # (C, N) replicated over rows (dim-sharded w/ dim_axis)
    mesh: jax.sharding.Mesh,
    axis: str,
    local_fn: Callable[[jax.Array, jax.Array], jax.Array],
    dim_axis: str | None = None,
) -> jax.Array:
    """(M_padded, C) pairwise L1 with M sharded over ``axis``; the caller
    slices the padded query rows off. With ``dim_axis`` the flat dim also
    shards: each shard's kernel body scores only its dim chunk (a partial
    L1 sum) and one ``psum`` over ``dim_axis`` yields the full matrix."""
    if dim_axis is None:
        return shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )(xs, centers)

    def body(x_local, c_local):
        return jax.lax.psum(local_fn(x_local, c_local), dim_axis)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, dim_axis), P(None, dim_axis)),
        out_specs=P(axis, None),
        check_vma=False,
    )(xs, centers)


def assign_sharded(
    u: jax.Array,  # (N,) arriving upload, replicated
    centers: jax.Array,  # (C_padded, N) center rows, sharded over ``axis``
    mesh: jax.sharding.Mesh,
    axis: str,
    local_dist_fn: Callable[[jax.Array, jax.Array], jax.Array],
    valid_rows: int | None = None,
    dim_axis: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sharded Eq. 1 argmin: (dists (C,), idx (), winning row (N,)).

    ``valid_rows`` is the true center count; the shard-padding rows above
    it are masked to ``+inf`` so they can never win the argmin. With
    ``dim_axis`` the upload and the center rows arrive dim-chunked: the
    kernel body scores each shard's chunk (a partial L1 sum) and a ``psum``
    over ``dim_axis`` completes the distances; the winning row comes back
    dim-sharded. The blend itself is left to the caller, which runs the
    single-device blend body on one device: the SPMD partitioner drops the
    optimization barrier that keeps that blend's two products apart, so a
    blend inside this program would be an FMA, an ulp off the plane's."""
    C = valid_rows if valid_rows is not None else centers.shape[0]

    def body(u_full, c_local):
        rows_local = c_local.shape[0]
        row0 = jax.lax.axis_index(axis) * rows_local
        d_local = local_dist_fn(u_full, c_local)
        if dim_axis is not None:
            d_local = jax.lax.psum(d_local, dim_axis)  # partial chunk sums
        gids = row0 + jnp.arange(rows_local)
        d_local = jnp.where(gids < C, d_local, jnp.inf)  # mask padded rows
        d_full = jax.lax.all_gather(d_local, axis).reshape(-1)
        idx = jnp.argmin(d_full).astype(jnp.int32)
        # one-hot cross-shard row fetch: only the owner contributes nonzero
        li = jnp.clip(idx - row0, 0, rows_local - 1)
        row = jax.lax.dynamic_index_in_dim(c_local, li, 0, keepdims=False)
        owned = (idx >= row0) & (idx < row0 + rows_local)
        row = jax.lax.psum(jnp.where(owned, row, 0.0), axis)
        return d_full, idx, row

    d_full, idx, row = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dim_axis), P(axis, dim_axis)),
        out_specs=(P(None), P(), P(dim_axis)),
        check_vma=False,
    )(u, centers)
    return d_full[:C], idx, row


def chi2_rows_sharded(
    f_pred: jax.Array,  # (M_padded, J) probe rows, sharded over ``axis``
    f_true: jax.Array,  # (M_padded, J)
    s_soft: jax.Array,  # (M_padded, J)
    mesh: jax.sharding.Mesh,
    axis: str,
    local_fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    dim_axis: str | None = None,
) -> jax.Array:
    """Sharded per-row feedback scores (the dissolve/expand probe matrix):
    every shard scores only its own probe rows — no reduction at all, the
    (M_padded,) output is row-sharded and reassembles on exit; the caller
    slices the padded rows off. With ``dim_axis`` the probe rows spread
    over BOTH mesh axes (the feedback operands have no model dim, so the
    model shards contribute row-parallelism; per-row arithmetic stays
    shard-local and bitwise)."""
    rows = (axis, dim_axis) if dim_axis is not None else axis
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(rows, None),) * 3,
        out_specs=P(rows),
        check_vma=False,
    )(f_pred, f_true, s_soft)


def chi2_all_sharded(
    f_pred: jax.Array,  # (M_padded, J) member rows, sharded over ``axis``
    f_true: jax.Array,  # (M_padded, J)
    s_soft: jax.Array,  # (M_padded, J)
    seg_onehot: jax.Array,  # (M_padded, S) membership one-hot; zero rows for padding
    mesh: jax.sharding.Mesh,
    axis: str,
    local_fn: Callable[..., tuple[jax.Array, jax.Array]],
    dim_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sharded segmented feedback: (g (M_padded,), seg_sum (S,) psum'd
    globally); the caller slices the padded member rows off ``g``. With
    ``dim_axis`` the member rows spread over BOTH mesh axes and the
    segment-sum psum runs over both (the partial chi2 contributions;
    per-member g stays shard-local and bitwise)."""
    rows = (axis, dim_axis) if dim_axis is not None else axis
    psum_axes = (axis, dim_axis) if dim_axis is not None else axis

    def body(fp_l, ft_l, ss_l, oh_l):
        g_local, seg_local = local_fn(fp_l, ft_l, ss_l, oh_l)
        return g_local, jax.lax.psum(seg_local, psum_axes)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(rows, None),) * 4,
        out_specs=(P(rows), P(None)),
        check_vma=False,
    )(f_pred, f_true, s_soft, seg_onehot)
