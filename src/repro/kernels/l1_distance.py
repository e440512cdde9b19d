"""On-arrival clustering distance (paper Eq. 1) as a Pallas TPU kernel.

Computes L1(u, v_c) for one arriving flattened parameter vector ``u``
against all ``C`` cluster centers. At assigned-architecture scale this is a
pure HBM-bandwidth-bound streaming reduction.

Layout: the flat dim is folded lane-dense into ``(rows, 128)`` (rows a
multiple of 8), so ``u`` streams as ``(block_r, 128)`` tiles and the
centers as ``(block_c, block_r, 128)`` tiles — every tile is whole
(8, 128) vregs. Each grid step folds ``|u - c|`` over groups of 8 rows into
an ``(8, 128)`` partial-sum tile per center: VPU adds only, accumulated in
the output block across the sequential row axis. The last 1024 -> 1 sum
per center runs in XLA on the ``(C, 8, 128)`` partials.

Grid: (C / block_c, rows / block_r), the row axis innermost.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def row_tiling(n: int, block_n: int) -> tuple[int, int]:
    """(rows, block_r) for a flat dim of ``n`` laid out as ``(rows, 128)``:
    one block of every row when they fit in ``block_n`` elements, else the
    fewest equal blocks of at most ``block_n`` elements, each a multiple of
    32 rows (the sublane tile of every dtype down to 8 bits)."""
    rows = math.ceil(n / (SUBLANES * LANES)) * SUBLANES
    max_r = max(SUBLANES, block_n // LANES)
    if rows <= max_r:
        return rows, rows
    blocks = math.ceil(rows / max_r)
    block_r = math.ceil(rows / blocks / 32) * 32
    return block_r * blocks, block_r


def to_lanes(x: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the last dim to ``rows * 128`` and fold it to ``(rows, 128)``
    (zero padding is exact for L1 and for the lerp, whose pad lanes are
    sliced off)."""
    pad = rows * LANES - x.shape[-1]
    x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x.reshape(*x.shape[:-1], rows, LANES)


def _l1_kernel(u_ref, c_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    u = u_ref[...].astype(jnp.float32)  # (block_r, 128)
    c = c_ref[...].astype(jnp.float32)  # (block_c, block_r, 128)
    d = jnp.abs(c - u[None])
    o_ref[...] += d.reshape(c.shape[0], -1, SUBLANES, LANES).sum(axis=1)


def l1_distance(
    u: jax.Array,  # (N,)
    centers: jax.Array,  # (C, N)
    *,
    block_n: int = 65536,
    block_c: int = 8,
    interpret: bool = False,
) -> jax.Array:
    (N,) = u.shape
    C = centers.shape[0]
    rows, block_r = row_tiling(N, block_n)
    block_c = min(block_c, C)
    c_p = math.ceil(C / block_c) * block_c
    up = to_lanes(u, rows)
    cp = to_lanes(jnp.pad(centers, ((0, c_p - C), (0, 0))), rows)

    out = pl.pallas_call(
        _l1_kernel,
        grid=(c_p // block_c, rows // block_r),
        in_specs=[
            pl.BlockSpec((block_r, LANES), lambda c, k: (k, 0)),
            pl.BlockSpec((block_c, block_r, LANES), lambda c, k: (c, k, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, SUBLANES, LANES), lambda c, k: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c_p, SUBLANES, LANES), jnp.float32),
        interpret=interpret,
    )(up, cp)
    return out[:C].sum(axis=(1, 2))
