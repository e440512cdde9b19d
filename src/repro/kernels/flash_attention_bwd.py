"""Flash attention backward pass as two Pallas TPU kernels.

The forward kernel saves only (o, lse); the backward recomputes each
(block_q x block_k) probability tile in VMEM — the classic recomputation
trade that keeps attention HBM traffic O(S^2 * d / block) in both passes.

  dq kernel : grid (B, H, nq, nk)   — inner loop over k blocks, dq tile
              accumulates in VMEM scratch, written once at the last ki.
  dkv kernel: grid (B, KV, nk, G*nq) — inner loop over (query-group, q
              block) pairs so GQA's dk/dv accumulate over all G query
              heads of the kv head without cross-core reductions.

Math per tile (recomputed exactly as the forward):
  s  = (q k^T) * scale ;  t = tanh(s / cap), s <- cap * t   (if softcap)
  p  = exp(s - lse)          (masked entries 0)
  dv += p^T do
  dp = do v^T
  ds = p * (dp - D),  D = rowsum(do * o)    (precomputed outside)
  ds <- ds * (1 - t^2)                       (softcap chain rule)
  dq += ds k * scale ;  dk += ds^T q * scale
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _tile_ds_p(q, k, lse_tile, *, scale, softcap, causal, window,
               q_pos0, q_pos_base, k_pos_base, q_len, kv_len, block_q, block_k,
               precision):
    """Recompute (p, s->ds chain factor, mask) for one tile. Returns
    (p, chain) where chain is d(softcap)/d(s_raw) (ones if no softcap)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=precision) * scale
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = softcap * t
        chain = 1.0 - t * t
    else:
        chain = None
    q_pos = q_pos0 + q_pos_base + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_pos_base + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_row = q_pos - q_pos0
    mask = (k_pos < kv_len) & (q_row < q_len)  # padded rows/cols contribute 0
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    p = jnp.where(mask, jnp.exp(s - lse_tile), 0.0)
    return p, chain


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref, dq_scr,
    *, scale, causal, window, softcap, block_q, block_k,
    q_pos0, num_k_blocks, q_len, kv_len, precision,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]  # (block_q, 1)
    dsum = dsum_ref[0, 0]

    p, chain = _tile_ds_p(
        q, k, lse, scale=scale, softcap=softcap, causal=causal, window=window,
        q_pos0=q_pos0, q_pos_base=qi * block_q, k_pos_base=ki * block_k,
        q_len=q_len, kv_len=kv_len, block_q=block_q, block_k=block_k,
        precision=precision,
    )
    dot = functools.partial(jax.lax.dot_general, precision=precision)
    dp = dot(do, v, (((1,), (1,)), ((), ())))  # (bq, bk)
    ds = p * (dp - dsum)
    if chain is not None:
        ds = ds * chain
    dq_scr[...] += dot(ds, k, (((1,), (0,)), ((), ()))) * scale

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, window, softcap, block_q, block_k,
    q_pos0, num_q_blocks, num_inner, q_len, kv_len, precision,
):
    ki = pl.program_id(2)
    gi = pl.program_id(3)  # linearized (query-group g, q block qi)
    qi = gi % num_q_blocks

    @pl.when(gi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]  # (block_q, 1)
    dsum = dsum_ref[0, 0]

    p, chain = _tile_ds_p(
        q, k, lse, scale=scale, softcap=softcap, causal=causal, window=window,
        q_pos0=q_pos0, q_pos_base=qi * block_q, k_pos_base=ki * block_k,
        q_len=q_len, kv_len=kv_len, block_q=block_q, block_k=block_k,
        precision=precision,
    )
    dot = functools.partial(jax.lax.dot_general, precision=precision)
    dv_scr[...] += dot(p, do, (((0,), (0,)), ((), ())))  # (bk, dv)
    dp = dot(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - dsum)
    if chain is not None:
        ds = ds * chain
    dk_scr[...] += dot(ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(gi == num_inner - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,   # (B, H, Sq, hd)
    k: jax.Array,   # (B, KV, Sk, hd)
    v: jax.Array,   # (B, KV, Sk, dv)
    o: jax.Array,   # (B, H, Sq, dv)
    lse: jax.Array,  # (B, H, Sq) fp32
    do: jax.Array,  # (B, H, Sq, dv)
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    softcap: float | None = None,
    q_pos0: int = 0,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, H, Sq, hd = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // KV
    scale = hd**-0.5 if scale is None else scale

    hd_p = math.ceil(hd / 128) * 128
    dv_p = math.ceil(dv / 128) * 128
    from repro.kernels.flash_attention import _blocks, dot_precision

    block_q, block_k = _blocks(Sq, Sk, block_q, block_k)
    sq_p = math.ceil(Sq / block_q) * block_q
    sk_p = math.ceil(Sk / block_k) * block_k
    nq, nk = sq_p // block_q, sk_p // block_k

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - Sq), (0, hd_p - hd)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - Sk), (0, hd_p - hd)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - Sk), (0, dv_p - dv)))
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, sq_p - Sq), (0, dv_p - dv)))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_p - Sq)))[..., None]
    # D = rowsum(do * o): tiny elementwise pre-pass outside the kernels
    dsum = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dsump = jnp.pad(dsum, ((0, 0), (0, 0), (0, sq_p - Sq)))[..., None]

    common = dict(scale=scale, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, q_pos0=q_pos0,
                  q_len=Sq, kv_len=Sk, precision=dot_precision(q.dtype))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k_blocks=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd_p), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd_p), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv_p), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dv_p), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd_p), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_p, hd_p), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd_p), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dsump)

    num_inner = G * nq
    dk, dvv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, num_inner=num_inner, **common),
        grid=(B, KV, nk, num_inner),
        in_specs=[
            # q/do/lse/dsum blocks walk over (g, qi); head = kv*G + g
            pl.BlockSpec((1, 1, block_q, hd_p),
                         lambda b, kv, ki, gi, g=G, n=nq: (b, kv * g + gi // n, gi % n, 0)),
            pl.BlockSpec((1, 1, block_k, hd_p), lambda b, kv, ki, gi: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv_p), lambda b, kv, ki, gi: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dv_p),
                         lambda b, kv, ki, gi, g=G, n=nq: (b, kv * g + gi // n, gi % n, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, kv, ki, gi, g=G, n=nq: (b, kv * g + gi // n, gi % n, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, kv, ki, gi, g=G, n=nq: (b, kv * g + gi // n, gi % n, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_k, hd_p), lambda b, kv, ki, gi: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv_p), lambda b, kv, ki, gi: (b, kv, ki, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, KV, sk_p, hd_p), k.dtype),
            jax.ShapeDtypeStruct((B, KV, sk_p, dv_p), v.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, hd_p), jnp.float32),
            pltpu.VMEM((block_k, dv_p), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dsump)

    return (
        dq[:, :, :Sq, :hd],
        dk[:, :, :Sk, :hd],
        dvv[:, :, :Sk, :dv],
    )
