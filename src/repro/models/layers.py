"""Layer primitives for the architecture zoo.

Everything is functional: ``init_*`` returns a params dict, ``apply_*``
consumes (params, activations, ...). Mixers optionally take/return a decode
cache; ``cache=None`` means full-sequence (train/prefill) mode.

Numerics policy: params in ``param_dtype``, matmuls in ``compute_dtype``,
softmax/gating/normalizers in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

PyTree = Any

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# Base matmuls of a narrow-weight model under float32 activations (a bf16
# base personalized by float32 adapters): the operands go in as the weight's
# dtype and the products accumulate in float32, in the forward pass and in
# the backward pass alike (the cotangent rounds to the weight's dtype as it
# enters each transposed product; the weight's gradient is returned in its
# dtype). One pass of the narrow operands is exact: the products ask for no
# more precision, whatever the default matmul precision (a grouped
# product's TPU kernel refuses more for bf16 operands).
_ONE_PASS = jax.lax.Precision.DEFAULT


def _one_pass(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, a, b, precision=_ONE_PASS, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mixed_einsum(eq, x, w):
    return _one_pass(eq, x.astype(w.dtype), w)


def _mixed_einsum_fwd(eq, x, w):
    return _mixed_einsum(eq, x, w), (x.astype(w.dtype), w)


def _mixed_einsum_bwd(eq, res, g):
    xn, w = res
    ins, out = eq.split("->")
    xs, ws = ins.split(",")
    gn = g.astype(w.dtype)
    return _one_pass(f"{out},{ws}->{xs}", gn, w), _one_pass(f"{xs},{out}->{ws}", xn, gn).astype(w.dtype)


_mixed_einsum.defvjp(_mixed_einsum_fwd, _mixed_einsum_bwd)

# the weight gradient of a grouped product: each group's rows contracted
_GROUPED_W = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])), lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@jax.custom_vjp
def _mixed_grouped(x, w, sizes):
    return jax.lax.ragged_dot(x.astype(w.dtype), w, sizes, precision=_ONE_PASS, preferred_element_type=jnp.float32)


def _mixed_grouped_fwd(x, w, sizes):
    return _mixed_grouped(x, w, sizes), (x.astype(w.dtype), w, sizes)


def _mixed_grouped_bwd(res, g):
    xn, w, sizes = res
    gn = g.astype(w.dtype)
    dx = jax.lax.ragged_dot(gn, jnp.swapaxes(w, 1, 2), sizes, precision=_ONE_PASS,
                            preferred_element_type=jnp.float32)
    dw = jax.lax.ragged_dot_general(xn, gn, sizes, _GROUPED_W, precision=_ONE_PASS,
                                    preferred_element_type=jnp.float32)
    return dx, dw.astype(w.dtype), None


_mixed_grouped.defvjp(_mixed_grouped_fwd, _mixed_grouped_bwd)


def _mixed(x: jax.Array, w: jax.Array) -> bool:
    return x.dtype == jnp.float32 and w.dtype != x.dtype


def _dot(eq: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``jnp.einsum(eq, x, w)`` for ``x`` times a base weight ``w``; a
    narrower weight under float32 activations multiplies in the weight's
    dtype and accumulates in float32."""
    return _mixed_einsum(eq, x, w) if _mixed(x, w) else jnp.einsum(eq, x, w)


def _grouped(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """Grouped product ``ragged_dot(x, w, sizes)``: rows of ``x`` sorted by
    group, ``sizes`` rows to each of ``w``'s groups; mixed precision as
    :func:`_dot`."""
    return _mixed_grouped(x, w, sizes) if _mixed(x, w) else jax.lax.ragged_dot(x, w, sizes)


def lora(x: jax.Array, ad: PyTree, scale: float) -> jax.Array:
    """A LoRA adapter on activations, ``scale * (x @ a) @ b``, never merged
    into the weight. ``a`` is (d, r) and ``b`` (r, k); with a leading client
    axis on both, ``x``'s leading (batch) axis is client-major, so every
    client's rows meet that client's factors while the base matmul beside
    it stays one product over all of them."""
    a, b = ad["a"], ad["b"]
    if a.ndim == 2:
        return scale * ((x @ a) @ b)
    xc = x.reshape(a.shape[0], -1, x.shape[-1])
    y = jnp.einsum("cnr,crk->cnk", jnp.einsum("cnd,cdr->cnr", xc, a), b)
    return scale * y.reshape(*x.shape[:-1], b.shape[-1])


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> PyTree:
    return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale)


def rms_norm(params: PyTree, x: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


# YaRN, as DeepSeek-V2's reference code (DeepseekV2YarnRotaryEmbedding)
# defines it for a rope part of ``dim`` dims, base theta, scaling factor s
# and L original positions:
#   f_extra(i) = theta^(-2i/dim), f_inter(i) = f_extra(i) / s, i < dim/2;
#   c(r) = dim * ln(L / (2 pi r)) / (2 ln theta), the dim whose wavelength
#   turns r times in L positions; lo = floor(c(beta_fast)),
#   hi = ceil(c(beta_slow)), clipped to [0, dim - 1];
#   m(i) = 1 - clamp((i - lo) / (hi - lo), 0, 1) (hi += 0.001 when equal);
#   inv_freq = f_inter * (1 - m) + f_extra * m: dims that turn often keep
#   their frequency, slow ones are interpolated by s;
#   g(s, mu) = 0.1 mu ln s + 1 (1 when s <= 1); cos and sin are scaled by
#   g(s, mscale) / g(s, mscale_all_dim) and the softmax scale by
#   g(s, mscale_all_dim)^2.
# DeepSeek-V2-Lite (dim 64, theta 10,000, s 40, L 4,096, beta 32 / 1,
# mscale 0.707 / 0.707): lo = floor(10.47) = 10, hi = ceil(22.50) = 23,
# cos/sin scale 1, softmax scale 192^-0.5 * 1.2608^2 = 0.11472.


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_range(dim: int, theta: float, scaling) -> tuple[int, int]:
    def c(rotations):
        return dim * math.log(scaling.original_max_position / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(c(scaling.beta_fast)), 0), min(math.ceil(c(scaling.beta_slow)), dim - 1)


def rope_inv_freq(dim: int, theta: float, scaling=None) -> jax.Array:
    """(dim/2,) rotation frequencies, YaRN-scaled when ``scaling`` is set."""
    half = dim // 2
    f_extra = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is None:
        return f_extra
    lo, hi = yarn_range(dim, theta, scaling)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo if hi > lo else 0.001), 0.0, 1.0)
    m = 1.0 - ramp
    return f_extra / scaling.factor * (1.0 - m) + f_extra * m


def mla_softmax_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def rope(x: jax.Array, positions: jax.Array, theta: float, scaling=None) -> jax.Array:
    """x: (..., S, H, hd) rotated pairwise; positions: (..., S); YaRN-scaled
    when ``scaling`` (a ``YarnSpec``) is set."""
    hd = x.shape[-1]
    half = hd // 2
    freq = rope_inv_freq(hd, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        g = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
        cos, sin = cos * g, sin * g
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (reference path — the Pallas flash kernel is the TPU hot path,
# selected in kernels/ops.py; this jnp version is the oracle + CPU path)
# ---------------------------------------------------------------------------


def attention_scores_reference(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    *,
    causal: bool,
    scale: float,
    window: int | None = None,
    softcap: float | None = None,
    q_pos0: jax.Array | int = 0,
    chunk_q: int | None = None,
) -> jax.Array:
    """Grouped-query attention with optional sliding window and logit softcap.

    KV heads are expanded to H before the einsums (Megatron-style KV
    replication). This keeps every activation's head dim == H, which GSPMD
    can shard over the model axis even when TP > KV (the (KV, G) grouped
    formulation blocks propagation there and silently replicates the O(S^2)
    attention compute — a 6x FLOP regression found in the dry-run roofline;
    see EXPERIMENTS.md §Perf iteration 1).

    For long sequences pass ``chunk_q`` to bound peak memory at
    O(chunk_q * Sk) instead of O(Sq * Sk) (memory-efficient attention).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if G != 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, k.shape[1], KV, G, k.shape[-1]))
        k = k.reshape(B, k.shape[1], H, k.shape[-1])
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, v.shape[1], KV, G, v.shape[-1]))
        v = v.reshape(B, v.shape[1], H, v.shape[-1])

    def block(q_blk, q_pos_blk):
        # q_blk: (B, sq, H, hd); scores (B, H, sq, Sk)
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k).astype(jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = jnp.arange(k.shape[1])
        mask = jnp.ones((q_blk.shape[1], k.shape[1]), bool)
        if causal:
            mask &= q_pos_blk[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos_blk[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshd->bqhd", p.astype(v.dtype), v)
        return o

    q_positions = q_pos0 + jnp.arange(Sq)
    if chunk_q is None or Sq <= chunk_q:
        out = block(q, q_positions)
    else:
        n = Sq // chunk_q
        qs = q[:, : n * chunk_q].reshape(B, n, chunk_q, H, hd)
        ps = q_positions[: n * chunk_q].reshape(n, chunk_q)
        out = jax.lax.map(lambda args: block(*args), (qs.swapaxes(0, 1), ps))
        out = out.swapaxes(0, 1).reshape(B, n * chunk_q, H, v.shape[-1])
        if n * chunk_q < Sq:  # ragged tail
            tail = block(q[:, n * chunk_q :], q_positions[n * chunk_q :])
            out = jnp.concatenate([out, tail], axis=1)
    return out


def init_attention(key, cfg: ModelConfig, dtype) -> PyTree:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wq": _dense_init(ks[0], (d, H, qk_dim), d, dtype),
            "w_dkv": _dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), d, dtype),
            "w_ukv": _dense_init(
                ks[2], (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim), m.kv_lora_rank, dtype
            ),
            "wo": _dense_init(ks[3], (H, m.v_head_dim, d), H * m.v_head_dim, dtype),
            "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        }
    return {
        "wq": _dense_init(ks[0], (d, H, hd), d, dtype),
        "wk": _dense_init(ks[1], (d, KV, hd), d, dtype),
        "wv": _dense_init(ks[2], (d, KV, hd), d, dtype),
        "wo": _dense_init(ks[3], (H, hd, d), H * hd, dtype),
    }


def apply_attention(
    params: PyTree,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    *,
    local: bool,
    cache: PyTree | None = None,
    pos0: jax.Array | int = 0,
    return_cache: bool = False,
    adapter: PyTree | None = None,
    lora_scale: float = 1.0,
):
    """Returns (out, new_cache). Cache layout:
      standard: {"k": (B, S_ctx, KV, hd), "v": ...}
      MLA:      {"ckv": (B, S_ctx, lora), "krope": (B, S_ctx, rope_dim)}
    In decode mode (cache is not None) S is the new-token count (1).
    ``adapter`` maps projection names (``wq``; MLA's ``w_dkv``) to LoRA
    factors added to that projection's output (:func:`lora`)."""
    if cfg.mla is not None:
        return _apply_mla(params, x, cfg, cache=cache, pos0=pos0, return_cache=return_cache,
                          adapter=adapter, lora_scale=lora_scale)
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if adapter is not None and "wq" in adapter:
        q = q + lora(x, adapter["wq"], lora_scale).reshape(q.shape).astype(q.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    positions = pos0 + jnp.arange(S)
    q = rope(q, positions, cfg.rope_theta) if not cfg.is_encoder else q
    k = rope(k, positions, cfg.rope_theta) if not cfg.is_encoder else k
    new_entries = {"k": k, "v": v}
    if cache is not None:
        k = jnp.concatenate([cache["k"], k], axis=1)
        v = jnp.concatenate([cache["v"], v], axis=1)
    scale = (
        cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar is not None else hd**-0.5
    )
    # flash kernels (fwd + bwd) via ops.attention — (B,H,S,hd) layout; falls
    # back to the materialized-S^2 reference under REPRO_KERNELS=ref
    from repro.kernels import ops as K

    out = K.attention(
        jnp.swapaxes(q, 1, 2),
        jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2),
        causal=cfg.causal,
        scale=scale,
        window=cfg.sliding_window if local else None,
        softcap=cfg.attn_logit_softcap,
        q_pos0=pos0,
    )
    out = jnp.einsum("bhsk,hkd->bsd", out, params["wo"])
    return out, (new_entries if return_cache else None)


def _apply_mla(params, x, cfg: ModelConfig, *, cache, pos0, return_cache, adapter=None, lora_scale=1.0):
    """DeepSeek-V2 multi-head latent attention. The cache holds only the
    compressed latent (kv_lora_rank) + shared rope key — the arch's whole
    point: 512+64 dims instead of 2*16*192 per token. The rope part is
    YaRN-scaled where the config says so; ``adapter`` may carry LoRA factors
    for ``wq`` and the latent down-projection ``w_dkv``."""
    m = cfg.mla
    adapter = adapter or {}
    with jax.named_scope("mla"):
        positions = pos0 + jnp.arange(x.shape[1])
        q = _dot("bsd,dhk->bshk", x, params["wq"])  # (B,S,H,nope+rope)
        if "wq" in adapter:
            q = q + lora(x, adapter["wq"], lora_scale).reshape(q.shape)
        q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
        q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

        dkv = _dot("bsd,dr->bsr", x, params["w_dkv"])  # (B,S,lora+rope)
        if "w_dkv" in adapter:
            dkv = dkv + lora(x, adapter["w_dkv"], lora_scale)
        ckv, k_rope = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
        ckv = rms_norm(params["kv_norm"], ckv, cfg.norm_eps)
        k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
        new_entries = {"ckv": ckv, "krope": k_rope}
        if cache is not None:
            ckv = jnp.concatenate([cache["ckv"], ckv], axis=1)
            k_rope = jnp.concatenate([cache["krope"], k_rope], axis=1)

        ukv = _dot("bsr,rhk->bshk", ckv, params["w_ukv"])
        k_nope = ukv[..., : m.qk_nope_head_dim]
        v = ukv[..., m.qk_nope_head_dim :]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (*k_nope.shape[:3], m.qk_rope_head_dim))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        from repro.kernels import ops as K

        out = K.attention(
            jnp.swapaxes(q_full, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            causal=cfg.causal, scale=mla_softmax_scale(cfg), q_pos0=pos0,
        )
        out = _dot("bhsk,hkd->bsd", out, params["wo"])
    return out, (new_entries if return_cache else None)


# ---------------------------------------------------------------------------
# dense + MoE FFN
# ---------------------------------------------------------------------------


def init_dense_ffn(key, d: int, d_ff: int, dtype) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": _dense_init(k1, (d, d_ff), d, dtype),
        "wu": _dense_init(k2, (d, d_ff), d, dtype),
        "wd": _dense_init(k3, (d_ff, d), d_ff, dtype),
    }


def apply_dense_ffn(params: PyTree, x: jax.Array) -> jax.Array:
    from repro.models import dist

    gate = jax.nn.silu(_dot("bsd,df->bsf", x, params["wg"]))
    up = dist.constrain(_dot("bsd,df->bsf", x, params["wu"]), "batch", None, "model")
    h = dist.constrain(gate * up, "batch", None, "model")
    return dist.constrain(_dot("bsf,fd->bsd", h, params["wd"]), "batch", None, None)


def init_moe_ffn(key, cfg: ModelConfig, dtype) -> PyTree:
    moe = cfg.moe
    d, de, E = cfg.d_model, moe.d_expert, moe.num_held  # the experts held here
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, moe.num_experts), d, jnp.float32),
        "wg": _dense_init(ks[1], (E, d, de), d, dtype),
        "wu": _dense_init(ks[2], (E, d, de), d, dtype),
        "wd": _dense_init(ks[3], (E, de, d), de, dtype),
    }
    if moe.num_shared:
        p["shared"] = init_dense_ffn(ks[4], d, moe.num_shared * de, dtype)
    return p


def _gate(params, xt, moe):
    """The router over all ``num_experts``: float32 logits and softmax,
    greedy top-k, weights renormalized only where ``norm_topk_prob`` says so,
    then scaled by ``routed_scaling_factor``. Returns (probs, top-k weights,
    top-k expert ids)."""
    logits = jnp.einsum("td,de->te", xt, params["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
    return probs, topw * moe.routed_scaling_factor, topi


def _balance_loss(probs, topi, E: int, K: int, valid=None) -> jax.Array:
    """Switch load-balancing loss, E * sum_e f_e * p_e / K, over all experts,
    per group (the leading axes of ``probs`` but the last two) of the tokens
    ``valid`` marks, averaged over groups."""
    density = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=-2)
    if valid is None:
        f, p = jnp.mean(density, axis=-2), jnp.mean(probs, axis=-2)
    else:
        count = jnp.sum(valid, axis=-1, keepdims=True)
        f = jnp.sum(density * valid[..., None], axis=-2) / count
        p = jnp.sum(probs * valid[..., None], axis=-2) / count
    return E * jnp.mean(jnp.sum(f * p, axis=-1)) / K


# Grouped products tile the rows of their operands by this many; the pair
# buffer's sizes below the top rung are rounded up to it.
_ROW_TILE = 128


def pair_ladder(T: int, moe) -> tuple[int, ...]:
    """Static sizes of :func:`apply_moe_held`'s pair buffer for ``T`` tokens,
    smallest first. The top rung, ``n = T * min(top_k, num_held)``, holds
    every pair a token can route to the held experts. A quarter and a half
    of it, rounded up to the row tile, are rungs below it where they hold at
    least twice the pairs a uniform router sends to the held experts,
    ``T * top_k * num_held / num_experts``. With every expert held every
    pair is live, and ``n`` is the only rung."""
    K, Eh, E = moe.top_k, moe.num_held, moe.num_experts
    n = T * min(K, Eh)
    room = 2 * T * K * Eh / E
    below = {-(-n // (d * _ROW_TILE)) * _ROW_TILE for d in (4, 2)}
    return tuple(sorted(r for r in below if room <= r < n)) + (n,)


def _held_pairs(rows: int, K: int, experts, xt, topw, order, key, sizes):
    """The held experts' part of the layer for ``xt`` (T, D) over the first
    ``rows`` pairs of ``order`` (the flat (token, k) pairs sorted by held
    expert, the pairs routed elsewhere last): gathered, run through the
    grouped products, scaled by the gate weight and scattered back onto
    their tokens."""
    Eh = sizes.shape[0]
    order = order[:rows]
    tok = order // K
    live = key[order] < Eh
    w = jnp.where(live, topw[order], 0.0)
    # rows past the groups (pairs not held) are left undefined by a grouped
    # product on some backends: select them away before and after each
    # product, so that neither they nor their gradients reach a token
    xs = jnp.where(live[:, None], xt[tok], 0.0)
    h = jax.nn.silu(_grouped(xs, experts["wg"], sizes)) * _grouped(xs, experts["wu"], sizes)
    h = jnp.where(live[:, None], h, 0.0)
    y = _grouped(h, experts["wd"], sizes)
    y = jnp.where(live[:, None], y * w[:, None].astype(y.dtype), 0.0)
    return jnp.zeros(xt.shape, y.dtype).at[tok].add(y)


def _rung(ladder: tuple[int, ...], sizes: jax.Array) -> jax.Array:
    """The smallest rung of ``ladder`` that holds the ``sizes.sum()`` held
    pairs."""
    return jnp.sum(jnp.sum(sizes) > jnp.asarray(ladder[:-1]), dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _laddered(ladder, K, experts, xt, topw, order, key, sizes):
    """:func:`_held_pairs` over the smallest rung of ``ladder`` that holds the
    held pairs, chosen on the device."""
    return jax.lax.switch(_rung(ladder, sizes), [functools.partial(_held_pairs, r, K) for r in ladder],
                          experts, xt, topw, order, key, sizes)


# The backward pass chooses its rung again and recomputes the rung's pairs:
# the residuals are the inputs, whatever the rung. (Differentiating the
# conditional itself would give every rung the residuals of all of them,
# the top rung's among them.)
def _laddered_fwd(ladder, K, *args):
    return _laddered(ladder, K, *args), args


def _laddered_bwd(ladder, K, args, g):
    experts, xt, topw, order, key, sizes = args
    # the barrier hands the conditional the weights as they are; without it
    # the TPU compiler lays out a transposed copy of the weights of every
    # period at once, ahead of the loop over periods, for the conditional
    experts = jax.lax.optimization_barrier(experts)

    def rung(rows):
        def pull(g, experts):
            _, vjp = jax.vjp(lambda e, x, w: _held_pairs(rows, K, e, x, w, order, key, sizes), experts, xt, topw)
            return vjp(g)
        return pull

    return (*jax.lax.switch(_rung(ladder, sizes), [rung(r) for r in ladder], g, experts), None, None, None)


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


def _routing(pairs: jax.Array, rung: jax.Array, ladder: tuple[int, ...]) -> dict:
    """An expert layer call's counts: each token's pairs computed (``pairs``),
    the call itself (``calls``), whether it ran its smallest buffer
    (``small``) and the buffer's rows (``rows``)."""
    return {"pairs": pairs, "calls": jnp.ones((), jnp.int32), "small": (rung == 0).astype(jnp.int32),
            "rows": jnp.asarray(ladder, jnp.int32)[rung]}


def no_routing(shape: tuple[int, int]) -> dict:
    """The counts of a layer without experts: :func:`_routing`'s, all zero."""
    zero = jnp.zeros((), jnp.int32)
    return {"pairs": jnp.zeros(shape, jnp.int32), "calls": zero, "small": zero, "rows": zero}


def apply_moe_held(params: PyTree, x: jax.Array, cfg: ModelConfig):
    """Dropless expert layer of one chip of an expert-parallel deployment.

    The router scores all ``num_experts``; this layer holds the
    ``num_held`` experts from ``held_first`` on (``params`` carries only
    theirs) and computes their part of the result for every (token, k) pair
    routed to them, none dropped: the pairs are sorted by expert and the
    three expert matmuls run as grouped products over the held experts
    (``ragged_dot``), scaled by the gate weight and scattered back onto their
    tokens. The shared experts run for every token. What the absent experts
    would add is left out; with every expert held this is the whole layer.

    The pair buffer runs the smallest rung of :func:`pair_ladder` that holds
    the call's held pairs, chosen on the device (a conditional); the sort
    puts those pairs first, so every rung computes the same rows.

    Returns (out, load-balancing loss, counts): ``pairs``, each token's
    (B, S) int32 count of pairs routed to held experts, and the call's
    buffer (:func:`_routing`)."""
    moe = cfg.moe
    B, S, D = x.shape
    E, K, Eh, first = moe.num_experts, moe.top_k, moe.num_held, moe.held_first
    xt = x.reshape(B * S, D)
    ladder = pair_ladder(B * S, moe)
    with jax.named_scope("moe"):
        probs, topw, topi = _gate(params, xt, moe)
        local = topi - first
        held = (local >= 0) & (local < Eh)
        key = jnp.where(held, local, Eh).reshape(-1)  # not held: sorts last
        # every held pair fits the top rung: a token's K experts are distinct
        order = jnp.argsort(key, stable=True)[:ladder[-1]]
        sizes = jnp.sum(jax.nn.one_hot(key, Eh, dtype=jnp.int32), axis=0)
        experts = {k: params[k] for k in ("wg", "wu", "wd")}
        args = (experts, xt, topw.reshape(-1), order, key, sizes)
        if len(ladder) == 1:
            rung = jnp.zeros((), jnp.int32)
            out = _held_pairs(ladder[0], K, *args)
        else:
            rung = _rung(ladder, sizes)
            out = _laddered(ladder, K, *args)
        out = out.reshape(B, S, D)
        if moe.num_shared:
            out = out + apply_dense_ffn(params["shared"], x)
    pairs = jnp.sum(held, axis=-1, dtype=jnp.int32).reshape(B, S)
    return out, _balance_loss(probs, topi, E, K), _routing(pairs, rung, ladder)


def apply_moe_ffn(
    params: PyTree,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    capacity_factor: float = 1.25,
    group_size: int = 4096,
) -> tuple[jax.Array, jax.Array, dict]:
    """GShard-style top-k dispatch MoE (expert-parallel friendly), or the
    dropless :func:`apply_moe_held` under ``cfg.moe_dropless``.

    Tokens are processed in groups; within a group each token routes to its
    top-k experts subject to per-expert capacity C = ceil(k*G*cf/E); overflow
    tokens fall through (residual connection carries them). The last group
    is padded with zero tokens, which queue behind every real token and so
    never take a real token's slot. Returns (out, aux_loss, counts): the
    standard load-balancing loss, and each token's count of dispatched pairs
    and the call's one buffer of ``E * C`` rows per group
    (:func:`_routing`).
    """
    if cfg.moe_dropless:
        return apply_moe_held(params, x, cfg)
    moe = cfg.moe
    if moe.num_held != moe.num_experts:
        raise ValueError("capacity dispatch holds every expert; a held share needs moe_dropless")
    B, S, D = x.shape
    E, K = moe.num_experts, moe.top_k
    T = B * S
    g = min(group_size, T)
    n_groups = -(-T // g)
    xt = x.reshape(T, D)
    if n_groups * g > T:
        xt = jnp.concatenate([xt, jnp.zeros((n_groups * g - T, D), xt.dtype)])
    xg = xt.reshape(n_groups, g, D)

    probs, topw, topi = _gate(params, xt, moe)
    probs, topw, topi = (a.reshape(n_groups, g, -1) for a in (probs, topw, topi))

    C = max(1, int(math.ceil(K * g * capacity_factor / E)))
    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)  # (n, g, K, E)
    flat = onehot.reshape(n_groups, g * K, E)
    pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(n_groups, g, K, E)
    keep = (pos_in_expert < C) * onehot
    slot = jnp.einsum("ngke,ngke->ngk", pos_in_expert, keep).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(slot, C, dtype=jnp.float32) * jnp.sum(keep, -1, keepdims=True)
    dispatch = jnp.einsum("ngke,ngkc->ngec", keep, slot_oh)  # (n, g, E, C)
    combine = jnp.einsum("ngk,ngke,ngkc->ngec", topw, keep, slot_oh)

    expert_in = jnp.einsum("ngec,ngd->necd", dispatch.astype(xg.dtype), xg)  # (n,E,C,D)
    h_g = jax.nn.silu(jnp.einsum("necd,edf->necf", expert_in, params["wg"]))
    h_u = jnp.einsum("necd,edf->necf", expert_in, params["wu"])
    expert_out = jnp.einsum("necf,efd->necd", h_g * h_u, params["wd"])
    out = jnp.einsum("ngec,necd->ngd", combine.astype(expert_out.dtype), expert_out)

    valid = None
    if n_groups * g > T:
        valid = (jnp.arange(n_groups * g) < T).astype(jnp.float32).reshape(n_groups, g)
    aux = _balance_loss(probs, topi, E, K, valid)
    routed = jnp.sum(keep, axis=(-2, -1)).reshape(-1)[:T].astype(jnp.int32).reshape(B, S)
    y = out.reshape(n_groups * g, D)[:T].reshape(B, S, D)
    if moe.num_shared:
        y = y + apply_dense_ffn(params["shared"], x)
    return y, aux, _routing(routed, jnp.zeros((), jnp.int32), (n_groups * E * C,))


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — chunked associative scan
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig, dtype) -> PyTree:
    mb = cfg.mamba
    d = cfg.d_model
    di, ds, dc = mb.d_inner(d), mb.d_state, mb.d_conv
    dt_rank = max(16, d // 16)
    ks = jax.random.split(key, 6)
    return {
        "w_in": _dense_init(ks[0], (d, 2 * di), d, dtype),
        "conv_w": _dense_init(ks[1], (dc, di), dc, dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "w_x": _dense_init(ks[2], (di, dt_rank + 2 * ds), di, dtype),
        "w_dt": _dense_init(ks[3], (dt_rank, di), dt_rank, dtype),
        "dt_bias": jnp.full((di,), -4.6, dtype),  # softplus^-1(0.01)
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))).astype(jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": _dense_init(ks[5], (di, d), di, dtype),
    }


def _mamba_conv(params, x_in, conv_state=None):
    """Causal depthwise conv. x_in: (B, S, Di). conv_state: (B, dc-1, Di)."""
    dc = params["conv_w"].shape[0]
    if conv_state is None:
        pad = jnp.zeros((x_in.shape[0], dc - 1, x_in.shape[2]), x_in.dtype)
    else:
        pad = conv_state.astype(x_in.dtype)
    xp = jnp.concatenate([pad, x_in], axis=1)  # (B, S+dc-1, Di)
    out = sum(
        xp[:, i : i + x_in.shape[1], :] * params["conv_w"][i][None, None, :] for i in range(dc)
    )
    new_state = xp[:, -(dc - 1) :, :]
    return out + params["conv_b"][None, None, :], new_state


def _mamba_ssm_inputs(params, xc, mb):
    dt_rank = params["w_dt"].shape[0]
    ds = mb.d_state
    proj = jnp.einsum("bsi,ir->bsr", xc, params["w_x"])
    dt_r, Bs, Cs = jnp.split(proj, [dt_rank, dt_rank + ds], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_r, params["w_dt"]).astype(jnp.float32)
        + params["dt_bias"].astype(jnp.float32)
    )  # (B,S,Di)
    A = -jnp.exp(params["A_log"])  # (Di, ds)
    dA = jnp.exp(dt[..., None] * A[None, None])  # (B,S,Di,ds)
    dBx = dt[..., None] * Bs[:, :, None, :].astype(jnp.float32) * xc[..., None].astype(jnp.float32)
    return dA, dBx, Cs.astype(jnp.float32)


def apply_mamba(
    params: PyTree,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    cache: PyTree | None = None,
    scan_chunk: int = 256,
):
    """Returns (out, new_cache). cache = {"conv": (B,dc-1,Di), "ssm": (B,Di,ds)}."""
    mb = cfg.mamba
    B, S, _ = x.shape
    xz = jnp.einsum("bsd,di->bsi", x, params["w_in"])
    x_in, z = jnp.split(xz, 2, axis=-1)

    if cache is not None and S == 1:  # decode step
        xc, conv_state = _mamba_conv(params, x_in, cache["conv"])
        xc = jax.nn.silu(xc)
        dA, dBx, Cs = _mamba_ssm_inputs(params, xc, mb)
        h = cache["ssm"] * dA[:, 0] + dBx[:, 0]  # (B,Di,ds)
        y = jnp.einsum("bis,bs->bi", h, Cs[:, 0])[:, None, :]
        new_cache = {"conv": conv_state, "ssm": h}
    else:
        xc, conv_state = _mamba_conv(params, x_in, cache["conv"] if cache else None)
        xc = jax.nn.silu(xc)
        h0 = cache["ssm"] if cache else jnp.zeros((B, x_in.shape[-1], mb.d_state), jnp.float32)

        def chunk_step(h_prev, xs):
            dA_c, dBx_c, Cs_c = xs  # (B, ck, Di, ds) ...
            # associative scan within the chunk
            def combine(a, b):
                return a[0] * b[0], b[0] * a[1] + b[1]

            pA, pB = jax.lax.associative_scan(combine, (dA_c, dBx_c), axis=1)
            h_all = pA * h_prev[:, None] + pB  # (B, ck, Di, ds)
            y_c = jnp.einsum("bcis,bcs->bci", h_all, Cs_c)
            return h_all[:, -1], y_c

        ck = min(scan_chunk, S)
        n = S // ck
        dA, dBx, Cs = _mamba_ssm_inputs(params, xc[:, : n * ck], mb)
        resh = lambda t: t.reshape(B, n, ck, *t.shape[2:]).swapaxes(0, 1)
        h_last, ys = jax.lax.scan(chunk_step, h0, (resh(dA), resh(dBx), resh(Cs)))
        y = ys.swapaxes(0, 1).reshape(B, n * ck, -1)
        if n * ck < S:  # ragged tail
            dA_t, dBx_t, Cs_t = _mamba_ssm_inputs(params, xc[:, n * ck :], mb)
            h_last, y_t = chunk_step(h_last, (dA_t, dBx_t, Cs_t))
            y = jnp.concatenate([y, y_t], axis=1)
        new_cache = {"conv": conv_state, "ssm": h_last}

    y = y.astype(x.dtype) + params["D"].astype(x.dtype)[None, None, :] * xc
    out = jnp.einsum("bsi,id->bsd", y * jax.nn.silu(z), params["w_out"])
    return out, new_cache


# ---------------------------------------------------------------------------
# xLSTM: mLSTM (chunkwise-parallel matrix memory) + sLSTM (sequential)
# ---------------------------------------------------------------------------


def init_mlstm(key, cfg: ModelConfig, dtype) -> PyTree:
    d = cfg.d_model
    di = int(d * cfg.mlstm_proj_factor)
    h = cfg.num_heads
    hd = di // h
    ks = jax.random.split(key, 7)
    return {
        "w_up": _dense_init(ks[0], (d, 2 * di), d, dtype),
        "wq": _dense_init(ks[1], (h, hd, hd), hd, dtype),  # block-diagonal per head
        "wk": _dense_init(ks[2], (h, hd, hd), hd, dtype),
        "wv": _dense_init(ks[3], (h, hd, hd), hd, dtype),
        "w_i": _dense_init(ks[4], (di, h), di, jnp.float32),
        "w_f": _dense_init(ks[5], (di, h), di, jnp.float32),
        "f_bias": jnp.full((h,), 3.0, jnp.float32),  # forget-gate bias toward remember
        "out_norm": init_rmsnorm(di, dtype),
        "w_down": _dense_init(ks[6], (di, d), di, dtype),
    }


def apply_mlstm(
    params: PyTree,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    cache: PyTree | None = None,
    chunk: int = 64,
):
    """Chunkwise-parallel mLSTM with stabilized exponential gating.

    cache = {"C": (B,h,hd,hd), "n": (B,h,hd), "m": (B,h)}. Within a chunk the
    output is computed attention-style with gate-derived decay masks; across
    chunks a lax.scan carries (C, n, m) — O(1) state in sequence length.
    """
    B, S, d = x.shape
    h = cfg.num_heads
    up = jnp.einsum("bsd,di->bsi", x, params["w_up"])
    x_in, z = jnp.split(up, 2, axis=-1)
    di = x_in.shape[-1]
    hd = di // h
    xh = x_in.reshape(B, S, h, hd)
    q = jnp.einsum("bshk,hkl->bshl", xh, params["wq"]) * (hd**-0.5)
    k = jnp.einsum("bshk,hkl->bshl", xh, params["wk"])
    v = jnp.einsum("bshk,hkl->bshl", xh, params["wv"])
    i_log = jnp.einsum("bsi,ih->bsh", x_in.astype(jnp.float32), params["w_i"])  # (B,S,h)
    f_log = jax.nn.log_sigmoid(
        jnp.einsum("bsi,ih->bsh", x_in.astype(jnp.float32), params["w_f"]) + params["f_bias"]
    )

    if cache is None:
        C0 = jnp.zeros((B, h, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, h, hd), jnp.float32)
        m0 = jnp.full((B, h), -1e30, jnp.float32)
    else:
        C0, n0, m0 = cache["C"], cache["n"], cache["m"]

    def chunk_step(carry, xs):
        C, n, m = carry
        qc, kc, vc, ic, fc = xs  # (B, ck, h, hd) / (B, ck, h)
        ck = qc.shape[1]
        fcum = jnp.cumsum(fc, axis=1)  # (B, ck, h) log decay within chunk
        # stabilizer: per-step running max of (m_prev + fcum) and (fcum - f_t + i_t)
        log_inter = m[:, None, :] + fcum  # contribution of carry state at step t
        log_intra = fcum[:, :, None, :] - fcum[:, None, :, :] + ic[:, None, :, :]
        # intra valid only for s <= t (causal within chunk): (B, t, s, h)
        tri = jnp.tril(jnp.ones((ck, ck), bool))
        log_intra = jnp.where(tri[None, :, :, None], log_intra, -jnp.inf)
        m_new = jnp.maximum(log_inter, jnp.max(log_intra, axis=2))  # (B, ck, h)
        m_new = jnp.maximum(m_new, -1e30)
        inter_w = jnp.exp(log_inter - m_new)  # (B, ck, h)
        intra_w = jnp.exp(log_intra - m_new[:, :, None, :])  # (B,t,s,h)
        # output: inter part reads carry memory, intra part is masked attention
        o_inter = jnp.einsum("bth,bhkl,bthk->bthl", inter_w, C, qc.astype(jnp.float32))
        n_inter = jnp.einsum("bth,bhk,bthk->bth", inter_w, n, qc.astype(jnp.float32))
        s_intra = jnp.einsum("bthk,bshk->btsh", qc.astype(jnp.float32), kc.astype(jnp.float32))
        o_intra = jnp.einsum("btsh,btsh,bshl->bthl", intra_w, s_intra, vc.astype(jnp.float32))
        n_intra = jnp.einsum("btsh,btsh->bth", intra_w, s_intra)
        denom = jnp.maximum(jnp.abs(n_inter + n_intra), jnp.exp(-m_new)) + 1e-6
        out_c = (o_inter + o_intra) / denom[..., None]
        # carry update to end of chunk
        ftot = fcum[:, -1, :]  # (B,h)
        m_next = jnp.maximum(m + ftot, jnp.max(fcum[:, -1:, :] - fcum + ic, axis=1))
        decay_keep = jnp.exp(m + ftot - m_next)  # (B,h)
        kv_w = jnp.exp(ftot[:, None, :] - fcum + ic - m_next[:, None, :])  # (B,ck,h)
        C_next = decay_keep[..., None, None] * C + jnp.einsum(
            "bsh,bshk,bshl->bhkl", kv_w, kc.astype(jnp.float32), vc.astype(jnp.float32)
        )
        n_next = decay_keep[..., None] * n + jnp.einsum("bsh,bshk->bhk", kv_w, kc.astype(jnp.float32))
        return (C_next, n_next, m_next), out_c

    ck = min(chunk, S)
    n_chunks = S // ck
    resh = lambda t: t[:, : n_chunks * ck].reshape(B, n_chunks, ck, *t.shape[2:]).swapaxes(0, 1)
    carry, outs = jax.lax.scan(chunk_step, (C0, n0, m0), (resh(q), resh(k), resh(v), resh(i_log), resh(f_log)))
    out = outs.swapaxes(0, 1).reshape(B, n_chunks * ck, h, hd)
    if n_chunks * ck < S:
        sl = slice(n_chunks * ck, None)
        carry, tail = chunk_step(carry, (q[:, sl], k[:, sl], v[:, sl], i_log[:, sl], f_log[:, sl]))
        out = jnp.concatenate([out, tail], axis=1)
    new_cache = {"C": carry[0], "n": carry[1], "m": carry[2]}

    out = out.reshape(B, S, di).astype(x.dtype)
    out = rms_norm(params["out_norm"], out, cfg.norm_eps)
    out = out * jax.nn.silu(z)
    return jnp.einsum("bsi,id->bsd", out, params["w_down"]), new_cache


def init_slstm(key, cfg: ModelConfig, dtype) -> PyTree:
    d = cfg.d_model
    df = int(d * cfg.slstm_proj_factor)
    ks = jax.random.split(key, 4)
    return {
        # gate-aligned (d, 4, d) layout: sharding the LAST dim over "model"
        # gives every device its own channel slice of all four gates, so the
        # recurrence runs fully local under shard_map (§Perf iteration C)
        "wgx": _dense_init(ks[0], (d, 4, d), d, dtype),  # i,f,z,o from input
        "wgh": _dense_init(ks[1], (d, 4, d), d, dtype),  # recurrent
        "gbias": jnp.zeros((4, d), jnp.float32),
        "ffn_up": _dense_init(ks[2], (d, df), d, dtype),
        "ffn_down": _dense_init(ks[3], (df, d), df, dtype),
    }


def apply_slstm(params: PyTree, x: jax.Array, cfg: ModelConfig, *, cache: PyTree | None = None):
    """Strictly sequential sLSTM with exponential gating + stabilizer state.

    cache = {"c": (B,D), "n": (B,D), "m": (B,D), "h": (B,D)}. No parallel
    form exists (the recurrence is non-associative through h_{t-1}) — this is
    inherent to the architecture, noted in DESIGN.md.
    """
    B, S, d = x.shape
    from repro.models import dist

    if cache is None:
        c0 = jnp.zeros((B, d), jnp.float32)
        n0 = jnp.full((B, d), 1e-6, jnp.float32)
        m0 = jnp.zeros((B, d), jnp.float32)
        h0 = jnp.zeros((B, d), x.dtype)
    else:
        c0, n0, m0, h0 = cache["c"], cache["n"], cache["m"], cache["h"]

    def recurrence(gx_loc, wh_loc, bias_loc, c0_, n0_, m0_, h0_, *, sharded: bool):
        """Time scan over channel-local shards. ``h`` is the only cross-
        channel coupling: it is all-gathered once per step (B x d, KBs)."""

        def step(carry, gx_t):
            c, n, m, h_full = carry
            gates = gx_t + jnp.einsum("bd,dgk->bgk", h_full, wh_loc) + bias_loc
            gates = gates.astype(jnp.float32)
            i_l, f_l, z_l, o_l = gates[:, 0], gates[:, 1], gates[:, 2], gates[:, 3]
            f_log = jax.nn.log_sigmoid(f_l)
            m_new = jnp.maximum(f_log + m, i_l)
            i_g = jnp.exp(i_l - m_new)
            f_g = jnp.exp(f_log + m - m_new)
            c_new = f_g * c + i_g * jnp.tanh(z_l)
            n_new = f_g * n + i_g
            h_new = (jax.nn.sigmoid(o_l) * c_new / jnp.maximum(n_new, 1e-6)).astype(h_full.dtype)
            if sharded:
                h_full_new = jax.lax.all_gather(h_new, "model", axis=1, tiled=True)
            else:
                h_full_new = h_new
            return (c_new, n_new, m_new, h_full_new), h_new

        (c, n, m, hf), hs = jax.lax.scan(
            step, (c0_, n0_, m0_, h0_), gx_loc.swapaxes(0, 1),
            unroll=8 if S >= 64 else 1,
        )
        return hs.swapaxes(0, 1), c, n, m, hs[-1]

    mesh = dist.current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    gx = jnp.einsum("bsd,dgk->bsgk", x, params["wgx"])  # (B,S,4,d) input part
    if mesh is not None and tp > 1 and d % tp == 0 and S > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        baxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
        dpn = 1
        for a in baxes:
            dpn *= mesh.shape[a]
        b_ax = baxes if B % dpn == 0 else None
        out_sm = shard_map(
            lambda gxl, whl, bl, c_, n_, m_, h_: recurrence(
                gxl, whl, bl, c_, n_, m_, h_, sharded=True
            ),
            mesh=mesh,
            in_specs=(
                P(b_ax, None, None, "model"),   # gx: channel-sharded
                P(None, None, "model"),          # w_h columns (gate-aligned)
                P(None, "model"),                # bias
                P(b_ax, "model"), P(b_ax, "model"), P(b_ax, "model"),  # c, n, m
                P(b_ax, None),                   # h replicated across model
            ),
            out_specs=(P(b_ax, None, "model"), P(b_ax, "model"), P(b_ax, "model"),
                       P(b_ax, "model"), P(b_ax, "model")),
            check_vma=False,
        )
        hs_out, c, n, m, h_last = out_sm(gx, params["wgh"], params["gbias"], c0, n0, m0, h0)
        out, h = hs_out, h_last
    else:
        out, c, n, m, h = recurrence(
            gx, params["wgh"], params["gbias"], c0, n0, m0, h0, sharded=False
        )
    out = out + jnp.einsum(
        "bsf,fd->bsd", jax.nn.gelu(jnp.einsum("bsd,df->bsf", out, params["ffn_up"])), params["ffn_down"]
    )
    return out, {"c": c, "n": n, "m": m, "h": h}
