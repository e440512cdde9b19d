"""Plain reference: LoRA training over a frozen MLA + MoE base.

DeepSeek-V2's decoder (arXiv:2405.04434, Sec. 2.1 multi-head latent
attention, Sec. 2.2 DeepSeekMoE) written out in ``jax.numpy``, in float32
under ``jax.default_matmul_precision("highest")``, one client's sequences
at a time. Nothing here imports the program; the caller hands in the base
as arrays, the sizes and the row's layout.

Per layer: ``x += MLA(rms(x))``, ``x += FFN(rms(x))``; then ``rms``, the
head, and the mean next-token NLL.

* RMSNorm: ``x / sqrt(mean(x^2) + eps) * weight``.
* MLA, no query compression: ``q = x wq (+ LoRA)``, ``[c, k_r] = x w_dkv
  (+ LoRA)``, ``c = rms(c)``, ``[k_n, v] = c w_ukv`` per head; the last
  ``qk_rope`` dims of q and the shared ``k_r`` are rotated (rotate-half
  layout) at YaRN-scaled frequencies; causal softmax attention at
  ``(nope + rope)^-0.5 * g(s, mscale_all_dim)^2``; ``o wo``.
* FFN: SwiGLU ``(silu(x wg) * (x wu)) wd``. In an expert layer: softmax over
  all routed experts' logits ``x router``, top-k, weights renormalized only
  where ``norm_topk_prob`` says so and scaled by ``routed_scaling_factor``;
  each held expert's SwiGLU for every token, masked by that token's weight
  for it; plus the shared experts' SwiGLU for every token.
* LoRA on activations: ``x a b / r`` beside the head's logits and the
  projections it targets.

Precision, as the configuration states: the base weights are bf16, and a
product with a base weight takes its other operand rounded to bf16 with
float32 accumulation, in the forward pass and in the backward pass (the
cotangent entering it rounds to bf16). A bf16 times bf16 product is exact
in float32, so these products are the float32 ones of the rounded
operands. Everything else is float32. ``dtype=bfloat16`` is the control:
every stored value, accumulation, norm, softmax, LoRA and SGD step in
bf16.

Departures from the published model: random weights; one chip's share of
an 8-way expert-parallel layer (the held experts only, the absent ones'
part left out) over a slice of the vocabulary; rope in rotate-half layout
(the published code de-interleaves q and k first: a fixed permutation of
the rope columns of ``wq`` and ``w_dkv``, immaterial under random weights).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(x):
    return x.astype(jnp.bfloat16)


@jax.custom_vjp
def _base_mm(x, w):
    """``x @ w`` for a bf16 base weight: bf16 operands, float32 sums."""
    return jnp.matmul(_bf16(x), w, preferred_element_type=jnp.float32)


def _base_mm_fwd(x, w):
    return _base_mm(x, w), w


def _base_mm_bwd(w, g):
    return jnp.matmul(_bf16(g), w.T, preferred_element_type=jnp.float32), None


_base_mm.defvjp(_base_mm_fwd, _base_mm_bwd)


def _mm(x, w, dt):
    """``x @ w`` for a base weight ``w`` (2-D) in the precision ``dt``."""
    if dt == jnp.float32:
        return _base_mm(x, w)
    return jnp.matmul(x.astype(dt), w.astype(dt), preferred_element_type=dt)


def _rms(x, weight, eps, dt):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True).astype(dt)
    return (x * jax.lax.rsqrt(var + eps) * weight.astype(dt)).astype(dt)


def yarn(sizes):
    """(inv_freq (rope/2,), cos/sin scale, softmax scale) of the rope part."""
    dim, theta = sizes["qk_rope"], sizes["rope_theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    f_extra = theta ** (-2 * i / dim)
    y = sizes.get("rope_scaling")
    scale = (sizes["qk_nope"] + dim) ** -0.5
    if not y:
        return f_extra, 1.0, scale

    def corr(r):
        return dim * math.log(y["original_max_position_embeddings"] / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((i - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the frequency stays; 0: divided by the factor
    inv = f_extra / y["factor"] * (1 - keep) + f_extra * keep

    def g(m):
        return 0.1 * m * math.log(y["factor"]) + 1.0 if y["factor"] > 1 else 1.0

    return inv, g(y["mscale"]) / g(y["mscale_all_dim"]), scale * g(y["mscale_all_dim"]) ** 2


def _rope(x, inv, mscale, dt):
    """Rotate the last axis of ``x`` (..., S, [H,] dim) pairwise, first half
    against second half; positions 0..S-1 on axis 1."""
    S, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    shape = (1, S) + (1,) * (x.ndim - 3) + (half,)
    cos = (jnp.cos(ang) * mscale).reshape(shape).astype(dt)
    sin = (jnp.sin(ang) * mscale).reshape(shape).astype(dt)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(dt)


def _lora(x, ad, r, dt):
    return (((x.astype(dt) @ ad["a"].astype(dt)).astype(dt) @ ad["b"].astype(dt)) / r).astype(dt)


def _swiglu(x, w, dt):
    return _mm(jax.nn.silu(_mm(x, w["wg"], dt)) * _mm(x, w["wu"], dt), w["wd"], dt).astype(dt)


def _mla(x, w, ad, sizes, dt):
    n, S, d = x.shape
    H, nope, rope, dv, kvr = (sizes[k] for k in ("heads", "qk_nope", "qk_rope", "v_head", "kv_lora"))
    r = sizes["lora_rank"]
    inv, mscale, scale = yarn(sizes)
    q = _mm(x, w["wq"].reshape(d, -1), dt)
    if "wq" in ad:
        q = q + _lora(x, ad["wq"], r, dt)
    q = q.reshape(n, S, H, nope + rope)
    kv = _mm(x, w["w_dkv"], dt)
    if "w_dkv" in ad:
        kv = kv + _lora(x, ad["w_dkv"], r, dt)
    c = _rms(kv[..., :kvr], w["kv_norm"], sizes["eps"], dt)
    k_r = _rope(kv[..., kvr:], inv, mscale, dt)  # (n, S, rope), one for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, mscale, dt)], axis=-1)
    ukv = _mm(c, w["w_ukv"].reshape(kvr, -1), dt).reshape(n, S, H, nope + dv)
    k = jnp.concatenate([ukv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (n, S, H, rope))], axis=-1)
    v = ukv[..., nope:]
    s = jnp.einsum("nqhe,nkhe->nhqk", q, k).astype(dt) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    o = jnp.einsum("nhqk,nkhe->nqhe", p, v).astype(dt)
    return _mm(o.reshape(n, S, H * dv), w["wo"].reshape(H * dv, d), dt)


def _experts(x, w, sizes, dt):
    """The held experts' part for every token, plus the shared experts."""
    E, K, first, held = sizes["experts"], sizes["top_k"], sizes["held_first"], sizes["experts_held"]
    logits = (x.astype(dt) @ w["router"].astype(dt)).astype(dt)  # all E routed experts
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)
    if sizes["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)
    top_w = (top_w * sizes["routed_scaling_factor"]).astype(dt)
    out = jnp.zeros(x.shape, dt)
    for e in range(held):
        weight = jnp.sum(jnp.where(top_i == first + e, top_w, 0), axis=-1).astype(dt)  # 0 where not routed
        ffn = {k: w[k][e] for k in ("wg", "wu", "wd")}
        out = out + weight[..., None] * _swiglu(x, ffn, dt)
    return (out + _swiglu(x, w["shared"], dt)).astype(dt)


def forward_nll(delta, base, tokens, labels, sizes, dt=jnp.float32):
    """Mean next-token NLL of one client's sequences ``tokens`` (n, S)."""
    eps, r = sizes["eps"], sizes["lora_rank"]
    x = base["embed"][tokens].astype(dt)
    for i, layer in enumerate(base["layers"]):
        x = (x + _mla(_rms(x, layer["norm1"], eps, dt), layer["attn"], delta["layers"][i], sizes, dt)).astype(dt)
        h = _rms(x, layer["norm2"], eps, dt)
        f = _swiglu(h, layer["ffn"], dt) if "router" not in layer["ffn"] else _experts(h, layer["ffn"], sizes, dt)
        x = (x + f).astype(dt)
    x = _rms(x, base["final_norm"], eps, dt)
    logits = (_mm(x, base["lm_head"], dt) + _lora(x, delta["head"], r, dt)).astype(dt)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked).astype(dt)


@functools.partial(jax.jit, static_argnames=("sizes", "epochs", "head_only", "dt"))
def _train(delta, base, tokens, labels, lr, *, sizes, epochs, head_only, dt):
    sizes = {k: dict(v) if k == "rope_scaling" and v else v for k, v in sizes}
    delta = jax.tree_util.tree_map(lambda a: a.astype(dt), delta)
    for _ in range(epochs):
        g = jax.grad(forward_nll)(delta, base, tokens, labels, sizes, dt)
        if head_only:
            g = {"head": g["head"], "layers": jax.tree_util.tree_map(jnp.zeros_like, g["layers"])}
        delta = jax.tree_util.tree_map(lambda a, b: (a - (lr * b).astype(dt)).astype(dt), delta, g)
    return delta


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict) else v) for k, v in sizes.items()))


def local_train(delta, base, tokens, labels, sizes: dict, *, epochs: int, lr: float, head_only: bool,
                dtype=jnp.float32):
    """``delta`` (``{"head": {a, b}, "layers": [{target: {a, b}}]}``) after
    ``epochs`` full-batch SGD steps at ``lr`` on one client's ``tokens`` and
    ``labels`` (n, S); with ``head_only`` the attention adapters stay."""
    with jax.default_matmul_precision("highest"):
        out = _train(delta, base, jnp.asarray(tokens), jnp.asarray(labels), jnp.float32(lr),
                     sizes=_frozen(sizes), epochs=int(epochs), head_only=bool(head_only),
                     dt=jnp.dtype(dtype))
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), out)
