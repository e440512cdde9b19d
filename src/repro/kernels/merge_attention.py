"""Cluster-merge weight aggregation (paper Algorithm 1) as Pallas TPU kernels.

Line 2-6 of Algorithm 1, fused over flattened parameter vectors:

    da     = v_aux - v_main          (assumed optimization direction)
    dp     = v_trained - v_main      (posterior direction after local pass)
    p      = da * dp                 (per-weight agreement)
    alpha  = relu(p) / max(p)        (attention map, global-max normalized)
    merged = alpha * v_aux + (1 - alpha) * v_main

A naive jnp composition makes 5 HBM round-trips over N elements; the fused
form needs exactly two passes (a max-reduction, then a blend that re-reads
the three inputs once and writes once) — the minimum possible given the
global normalizer. The vectors stream lane-dense as ``(rows, 128)`` tiles
(``l1_distance.row_tiling``). Pass 1 keeps an elementwise running max in an
(8, 128) output tile across the sequential grid; pass 2 reduces that tile
to the global max and runs a pure elementwise VPU blend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.l1_distance import LANES, SUBLANES, row_tiling, to_lanes

_NEG = -3.4e38


def _max_kernel(vm_ref, va_ref, vt_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _NEG)

    vm = vm_ref[...].astype(jnp.float32)
    p = (va_ref[...].astype(jnp.float32) - vm) * (vt_ref[...].astype(jnp.float32) - vm)
    o_ref[...] = jnp.maximum(o_ref[...], p.reshape(-1, SUBLANES, LANES).max(axis=0))


def _blend_kernel(vm_ref, va_ref, vt_ref, pmax_ref, o_ref):
    vm = vm_ref[...].astype(jnp.float32)
    va = va_ref[...].astype(jnp.float32)
    p = (va - vm) * (vt_ref[...].astype(jnp.float32) - vm)
    denom = jnp.maximum(jnp.max(pmax_ref[...]), 1e-12)
    alpha = jnp.maximum(p, 0.0) / denom
    o_ref[...] = (alpha * va + (1.0 - alpha) * vm).astype(o_ref.dtype)


def merge_attention(
    v_main: jax.Array,  # (N,)
    v_aux: jax.Array,  # (N,)
    v_trained: jax.Array,  # (N,) main model after one local training pass
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> jax.Array:
    (N,) = v_main.shape
    rows, block_r = row_tiling(N, block_n)
    vm, va, vt = (to_lanes(x, rows) for x in (v_main, v_aux, v_trained))
    nk = rows // block_r
    vec_spec = pl.BlockSpec((block_r, LANES), lambda k: (k, 0))
    max_spec = pl.BlockSpec((SUBLANES, LANES), lambda k: (0, 0))

    # Padding note: padded lanes give p = 0, which only matters if every real
    # p < 0; relu() zeroes those lanes in the blend anyway, so exactness holds.
    pmax = pl.pallas_call(
        _max_kernel,
        grid=(nk,),
        in_specs=[vec_spec, vec_spec, vec_spec],
        out_specs=max_spec,
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32),
        interpret=interpret,
    )(vm, va, vt)

    merged = pl.pallas_call(
        _blend_kernel,
        grid=(nk,),
        in_specs=[vec_spec, vec_spec, vec_spec, max_spec],
        out_specs=vec_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), v_main.dtype),
        interpret=interpret,
    )(vm, va, vt, pmax)
    return merged.reshape(-1)[:N]
