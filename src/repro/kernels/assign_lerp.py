"""Fused on-arrival assignment + mixed-rate center update (Eq. 1 + Sec. 4).

Every upload triggers the same two hot steps: find the L1-nearest center,
then blend the upload into it at the mix rate b. ``assign_and_lerp`` fuses
them into one device-resident pass: the streaming one-vs-many L1 kernel
produces the distance vector, the argmin stays on device, and a
scalar-prefetch kernel reads *only* the winning center row (the argmin
index steers the BlockSpec index map) to emit the blended row — the full
(C, N) center matrix is never re-read, and nothing round-trips through the
host between distance, argmin, and update.

The caller applies hysteresis host-side: when the argmin is vetoed (the
client stays in its previous cluster), the precomputed blended row is
simply discarded and a plain row lerp runs instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l1_distance import LANES, l1_distance, row_tiling, to_lanes


def _select_lerp_kernel(idx_ref, c_ref, u_ref, o_ref, *, beta: float, fence):
    del idx_ref  # consumed by the index maps
    c = c_ref[...].astype(jnp.float32)  # (block_r, 128) of the winning row
    u = u_ref[...].astype(jnp.float32)
    # two-op form pinned (no FMA contraction): the blend must emit the same
    # bits as plane.lerp_vec and the coalesced ingest scan, whatever fusion
    # context this kernel lowers in (see assign_and_lerp_ref's rationale)
    m1, m2 = fence(((1.0 - beta) * c, beta * u))
    o_ref[...] = m1 + m2


def select_lerp(
    centers: jax.Array,  # (C, N)
    u: jax.Array,  # (N,)
    idx: jax.Array,  # () int32 — which center row to blend
    beta: float,
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> jax.Array:
    """``(1 - beta) * centers[idx] + beta * u`` reading only row ``idx``."""
    C, N = centers.shape
    rows, block_r = row_tiling(N, block_n)
    cp = to_lanes(centers, rows)  # (C, rows, 128)
    up = to_lanes(u, rows)  # (rows, 128)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((pl.squeezed, block_r, LANES), lambda r, idx_ref: (idx_ref[0], r, 0)),
            pl.BlockSpec((block_r, LANES), lambda r, idx_ref: (r, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, LANES), lambda r, idx_ref: (r, 0)),
    )
    # the interpreter runs the body through XLA, which would contract the
    # blend into an FMA without the barrier; Mosaic has no lowering for it
    fence = jax.lax.optimization_barrier if interpret else (lambda x: x)
    out = pl.pallas_call(
        functools.partial(_select_lerp_kernel, beta=beta, fence=fence),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(idx, (1,)).astype(jnp.int32), cp, up)
    return out.reshape(-1)[:N]


def assign_and_lerp(
    u: jax.Array,  # (N,) arriving flattened upload
    centers: jax.Array,  # (C, N) stacked cluster centers (plane rows)
    beta: float,  # mix rate b
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (dists (C,) fp32, idx () int32 argmin, blended (N,) fp32)
    where ``blended = (1 - beta) * centers[idx] + beta * u``."""
    dists = l1_distance(u, centers, block_n=block_n, interpret=interpret)
    idx = jnp.argmin(dists).astype(jnp.int32)
    blended = select_lerp(centers, u, idx, beta, block_n=block_n, interpret=interpret)
    return dists, idx, blended
