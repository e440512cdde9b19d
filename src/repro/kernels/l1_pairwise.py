"""Many-vs-many L1 distance (Eq. 1, batched) as a tiled Pallas TPU kernel.

``l1_distance_pairwise(xs, cs)`` computes the (M, C) matrix of L1 distances
between every query vector and every center in a single launch — the merge
candidate search (``nearest_pair``), feedback-corrective reassignment, and
cluster dissolution all reduce to one call on the plane's stacked rows,
where the seed implementation looped the one-vs-many kernel row by row.

Grid: (M / block_m, C / block_c, N / block_n); the innermost n-dimension is
sequential, so each (block_m, block_c) output tile accumulates its partial
sums in fp32 across n-steps. The centers sit on the output's lanes: a tile
holds every center when C <= 128 (the full dim), else 128 of them, so the
output block is always legal and lane-dense. Inside a step the kernel walks
the tile's centers one row at a time — ``|x - c_j|`` over the
(block_m, block_n) query tile, a lane reduction to one column, and a select
into lane ``j`` of the accumulator — so no (block_m, block_c, block_n)
intermediate is ever built.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_kernel(x_ref, c_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (block_m, block_n)
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def one_center(j, acc):
        c = c_ref[pl.ds(j, 1), :].astype(jnp.float32)  # (1, block_n)
        d = jnp.sum(jnp.abs(x - c), axis=1, keepdims=True)  # (block_m, 1)
        return acc + jnp.where(lane == j, d, 0.0)

    o_ref[...] += jax.lax.fori_loop(
        0, c_ref.shape[0], one_center, jnp.zeros(o_ref.shape, jnp.float32)
    )


def l1_distance_pairwise(
    xs: jax.Array,  # (M, N)
    centers: jax.Array,  # (C, N)
    *,
    block_m: int = 8,
    block_c: int = 128,
    block_n: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    M, N = xs.shape
    C = centers.shape[0]
    block_m = min(block_m, max(1, 1 << (M - 1).bit_length()))
    block_c = min(block_c, C)
    block_n = min(block_n, max(128, 1 << (N - 1).bit_length()))
    m_p = math.ceil(M / block_m) * block_m
    c_p = math.ceil(C / block_c) * block_c
    n_p = math.ceil(N / block_n) * block_n
    # Zero padding in N is exact for L1; padded M/C rows are sliced off.
    xp = jnp.pad(xs, ((0, m_p - M), (0, n_p - N)))
    cp = jnp.pad(centers, ((0, c_p - C), (0, n_p - N)))

    out = pl.pallas_call(
        _pairwise_kernel,
        grid=(m_p // block_m, c_p // block_c, n_p // block_n),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda m, c, n: (m, n)),
            pl.BlockSpec((block_c, block_n), lambda m, c, n: (c, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_c), lambda m, c, n: (m, c)),
        out_shape=jax.ShapeDtypeStruct((m_p, c_p), jnp.float32),
        interpret=interpret,
    )(xp, cp)
    return out[:M, :C]
