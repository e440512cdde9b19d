"""Flash attention (causal / sliding-window / softcap, GQA, dv != dk) as a
Pallas TPU kernel with explicit BlockSpec VMEM tiling.

TPU adaptation (vs. the CUDA flash-attention algorithm): one fused pass with
online softmax; the (block_q x block_k) tile pair lives in VMEM, the MXU
consumes (block_q, hd) x (hd, block_k) matmuls with hd padded to a lane
multiple of 128, and the running (m, l, acc) statistics sit in VMEM scratch
that persists across the sequential innermost grid dimension (TPU grids
execute serially per core, so scratch carries state instead of CUDA's
shared-memory reductions).

The kernel also emits the log-sum-exp rows (a ``(block_q, 1)`` column per
tile, the layout of the running max), which the backward kernels
(flash_attention_bwd.py) consume to recompute probability tiles instead of
storing the O(S^2) matrix — that recomputation is what keeps attention HBM
traffic at O(S^2 * d / block) instead of O(S^2).

Grid: (B, H, Sq/block_q, Sk/block_k) — the k-block axis is innermost.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def dot_precision(dtype) -> jax.lax.Precision:
    """Precision of the kernels' f32 dots. The MXU's default is one bf16
    pass, which rounds f32 operands: f32 inputs take the full-f32 passes.
    Narrower inputs keep the single pass (their q/k/v values are exact in
    bf16; the probability tiles round, as in any bf16 flash kernel)."""
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, softcap: float | None,
    block_q: int, block_k: int, q_pos0: int, num_k_blocks: int, kv_len: int,
    precision: jax.lax.Precision,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (block_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (block_k, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=precision) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_pos0 + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len  # never attend to padded key slots
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (block_q, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # (bq, bk)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=precision)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l_safe)


def _blocks(sq: int, sk: int, block_q: int, block_k: int) -> tuple[int, int]:
    bq = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (sk - 1).bit_length()))
    return bq, bk


def flash_attention_with_lse(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KV, Sk, hd)
    v: jax.Array,  # (B, KV, Sk, dv)
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    softcap: float | None = None,
    q_pos0: int = 0,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (o: (B,H,Sq,dv), lse: (B,H,Sq) fp32)."""
    B, H, Sq, hd = q.shape
    KV, Sk, dv = k.shape[1], k.shape[2], v.shape[3]
    if H % KV != 0:
        raise ValueError(f"GQA requires num_heads ({H}) divisible by kv_heads ({KV})")
    if k.shape[:3] != v.shape[:3] or k.shape[0] != B or k.shape[3] != hd:
        raise ValueError(f"inconsistent shapes q={q.shape} k={k.shape} v={v.shape}")
    G = H // KV
    scale = hd**-0.5 if scale is None else scale

    # Pad to hardware-aligned tiles: head dims to 128 lanes, seqs to blocks.
    hd_p = math.ceil(hd / 128) * 128
    dv_p = math.ceil(dv / 128) * 128
    block_q, block_k = _blocks(Sq, Sk, block_q, block_k)
    sq_p = math.ceil(Sq / block_q) * block_q
    sk_p = math.ceil(Sk / block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - Sq), (0, hd_p - hd)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - Sk), (0, hd_p - hd)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - Sk), (0, dv_p - dv)))
    nq, nk = sq_p // block_q, sk_p // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, q_pos0=q_pos0, num_k_blocks=nk, kv_len=Sk,
        precision=dot_precision(q.dtype),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd_p), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd_p), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv_p), lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, dv_p), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, sq_p, dv_p), q.dtype),
            jax.ShapeDtypeStruct((B, H, sq_p, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # running sum l
            pltpu.VMEM((block_q, dv_p), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :, :Sq, :dv], lse[:, :, :Sq, 0]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    window: int | None = None,
    softcap: float | None = None,
    q_pos0: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    o, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
        q_pos0=q_pos0, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return o
