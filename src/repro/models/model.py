"""Model assembly: init / forward / cache for every assigned architecture.

The backbone is ``prefix`` (unrolled) + ``pattern`` × ``num_periods``
(lax.scan over stacked params — O(1) HLO in depth, so the 126-layer model
compiles as fast as the 16-layer one). Decode uses fixed-size KV/state
buffers updated in place (donation-friendly: no cache reallocation per step).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import layers as L

PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype) -> PyTree:
    k_mix, k_ffn = jax.random.split(key)
    p: dict[str, Any] = {"norm1": L.init_rmsnorm(cfg.d_model, dtype)}
    if spec.mixer in ("attn", "attn_local"):
        p["mixer"] = L.init_attention(k_mix, cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = L.init_mamba(k_mix, cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = L.init_mlstm(k_mix, cfg, dtype)
    elif spec.mixer == "slstm":
        p["mixer"] = L.init_slstm(k_mix, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dtype)
        if spec.ffn == "dense":
            p["ffn"] = L.init_dense_ffn(k_ffn, cfg.d_model, cfg.d_ff, dtype)
        else:
            p["ffn"] = L.init_moe_ffn(k_ffn, cfg, dtype)
    if cfg.use_post_norm:
        p["post_norm1"] = L.init_rmsnorm(cfg.d_model, dtype)
        if spec.ffn != "none":
            p["post_norm2"] = L.init_rmsnorm(cfg.d_model, dtype)
    return p


def _init_period(key, cfg: ModelConfig, dtype) -> PyTree:
    keys = jax.random.split(key, len(cfg.pattern))
    return {f"slot{i}": _init_layer(keys[i], spec, cfg, dtype) for i, spec in enumerate(cfg.pattern)}


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> PyTree:
    k_embed, k_prefix, k_body, k_head = jax.random.split(key, 4)
    embed_scale = 1.0 / math.sqrt(cfg.d_model)  # keeps tied-logit variance O(1)
    params: dict[str, Any] = {
        "embed": (
            jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model), jnp.float32) * embed_scale
        ).astype(dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.prefix:
        pkeys = jax.random.split(k_prefix, len(cfg.prefix))
        params["prefix"] = [
            _init_layer(pkeys[i], spec, cfg, dtype) for i, spec in enumerate(cfg.prefix)
        ]
    if cfg.num_periods:
        bkeys = jax.random.split(k_body, cfg.num_periods)
        params["blocks"] = jax.vmap(lambda k: _init_period(k, cfg, dtype))(bkeys)
    if not cfg.tie_embeddings:
        params["lm_head"] = L._dense_init(k_head, (cfg.d_model, cfg.padded_vocab), cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# caches (decode buffers)
# ---------------------------------------------------------------------------


def _init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, buf_len: int, dtype) -> PyTree:
    if spec.mixer in ("attn", "attn_local"):
        if cfg.mla is not None:
            m = cfg.mla
            return {
                "ckv": jnp.zeros((batch, buf_len, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((batch, buf_len, m.qk_rope_head_dim), dtype),
            }
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, buf_len, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, buf_len, cfg.num_kv_heads, hd), dtype),
        }
    if spec.mixer == "mamba":
        di = cfg.mamba.d_inner(cfg.d_model)
        return {
            "conv": jnp.zeros((batch, cfg.mamba.d_conv - 1, di), dtype),
            "ssm": jnp.zeros((batch, di, cfg.mamba.d_state), jnp.float32),
        }
    if spec.mixer == "mlstm":
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        h = cfg.num_heads
        hd = di // h
        return {
            "C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32),
            "m": jnp.full((batch, h), -1e30, jnp.float32),
        }
    if spec.mixer == "slstm":
        d = cfg.d_model
        return {
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.full((batch, d), 1e-6, jnp.float32),
            "m": jnp.zeros((batch, d), jnp.float32),
            "h": jnp.zeros((batch, d), dtype),
        }
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, ctx_len: int, dtype=jnp.float32, margin: int = 128) -> PyTree:
    """Fixed-size decode buffers sized for ``ctx_len`` context + ``margin``
    generated tokens. ``len`` counts valid tokens already cached."""
    buf = ctx_len + margin
    cache: dict[str, Any] = {"len": jnp.asarray(ctx_len, jnp.int32)}
    if cfg.prefix:
        cache["prefix"] = [
            _init_layer_cache(spec, cfg, batch, buf, dtype) for spec in cfg.prefix
        ]
    if cfg.num_periods:
        def one(_):
            return {
                f"slot{i}": _init_layer_cache(spec, cfg, batch, buf, dtype)
                for i, spec in enumerate(cfg.pattern)
            }
        cache["blocks"] = jax.vmap(one)(jnp.arange(cfg.num_periods))
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _apply_layer(
    lp: PyTree, spec: LayerSpec, cfg: ModelConfig, x, *, cache, pos0, decode, collect=False,
    adapter=None, lora_scale=1.0,
):
    h = L.rms_norm(lp["norm1"], x, cfg.norm_eps)
    if spec.mixer in ("attn", "attn_local"):
        if decode:
            mix, new_cache = _attn_decode(lp["mixer"], h, cfg, spec.mixer == "attn_local", cache, pos0)
        else:
            mix, new_cache = L.apply_attention(
                lp["mixer"], h, cfg, local=spec.mixer == "attn_local", pos0=pos0,
                return_cache=collect, adapter=adapter, lora_scale=lora_scale,
            )
    elif spec.mixer == "mamba":
        mix, new_cache = L.apply_mamba(lp["mixer"], h, cfg, cache=cache)
    elif spec.mixer == "mlstm":
        mix, new_cache = L.apply_mlstm(lp["mixer"], h, cfg, cache=cache)
    elif spec.mixer == "slstm":
        mix, new_cache = L.apply_slstm(lp["mixer"], h, cfg, cache=cache)
    else:
        raise ValueError(spec.mixer)
    if cfg.use_post_norm:
        mix = L.rms_norm(lp["post_norm1"], mix, cfg.norm_eps)
    from repro.models import dist

    # pin the residual stream batch-sharded / d_model-replicated: left free,
    # GSPMD shards it over "model", turning every D-contraction into
    # full-d_ff partial sums + all-reduce (§Perf iteration 2)
    x = dist.constrain(x + mix, "batch", None, None)
    aux = jnp.zeros((), jnp.float32)
    routed = L.no_routing(x.shape[:2])
    if spec.ffn != "none":
        h2 = L.rms_norm(lp["norm2"], x, cfg.norm_eps)
        if spec.ffn == "dense":
            f = L.apply_dense_ffn(lp["ffn"], h2)
        else:
            f, aux, routed = L.apply_moe_ffn(lp["ffn"], h2, cfg)
        if cfg.use_post_norm:
            f = L.rms_norm(lp["post_norm2"], f, cfg.norm_eps)
        x = dist.constrain(x + f, "batch", None, None)
    return x, new_cache, (aux, routed)


def _attn_decode(mp, h, cfg: ModelConfig, local: bool, cache, pos0):
    """One-token attention against the fixed-size buffer, in-place update."""
    B = h.shape[0]
    if cfg.mla is not None:
        return _mla_decode_absorbed(mp, h, cfg, cache, pos0)
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", h, mp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, mp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, mp["wv"])
    positions = pos0 + jnp.arange(1)
    if not cfg.is_encoder:
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    k_buf = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos0, 0, 0))
    v_buf = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos0, 0, 0))
    scale = cfg.query_pre_attn_scalar ** -0.5 if cfg.query_pre_attn_scalar is not None else hd**-0.5
    out = L.attention_scores_reference(
        q, k_buf.astype(h.dtype), v_buf.astype(h.dtype),
        causal=True, scale=scale,
        window=cfg.sliding_window if local else None,
        softcap=cfg.attn_logit_softcap, q_pos0=pos0,
    )
    out = jnp.einsum("bshk,hkd->bsd", out, mp["wo"])
    return out, {"k": k_buf, "v": v_buf}


def _mla_decode_absorbed(mp, h, cfg: ModelConfig, cache, pos0):
    """MLA decode with weight absorption: attention runs directly in the
    512-dim latent space — the cache is never up-projected. This is the
    beyond-naive decode path (see EXPERIMENTS.md §Perf)."""
    m = cfg.mla
    H = cfg.num_heads
    q = jnp.einsum("bsd,dhk->bshk", h, mp["wq"])  # (B,1,H,nope+rope)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    positions = pos0 + jnp.arange(1)
    q_rope = L.rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    dkv = jnp.einsum("bsd,dr->bsr", h, mp["w_dkv"])
    ckv_new, krope_new = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    ckv_new = L.rms_norm(mp["kv_norm"], ckv_new, cfg.norm_eps)
    krope_new = L.rope(krope_new[:, :, None, :], positions, cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    ckv_buf = jax.lax.dynamic_update_slice(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), (0, pos0, 0))
    krope_buf = jax.lax.dynamic_update_slice(
        cache["krope"], krope_new.astype(cache["krope"].dtype), (0, pos0, 0)
    )

    w_uk = mp["w_ukv"][..., : m.qk_nope_head_dim]  # (lora, H, nope)
    w_uv = mp["w_ukv"][..., m.qk_nope_head_dim :]  # (lora, H, v)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, w_uk)  # absorb: q in latent space
    s_nope = jnp.einsum("bshr,btr->bhst", q_abs, ckv_buf.astype(q_abs.dtype))
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, krope_buf.astype(q_rope.dtype))
    s = (s_nope + s_rope).astype(jnp.float32) * L.mla_softmax_scale(cfg)
    t_pos = jnp.arange(ckv_buf.shape[1])
    s = jnp.where((t_pos <= pos0)[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", p.astype(ckv_buf.dtype), ckv_buf)
    out = jnp.einsum("bshr,rhk->bshk", o_lat, w_uv)  # back to per-head v space
    out = jnp.einsum("bshk,hkd->bsd", out, mp["wo"])
    return out, {"ckv": ckv_buf, "krope": krope_buf}


def _sinusoidal(seq: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle)).at[:, 1::2].set(jnp.cos(angle[:, : d // 2]))
    return pe.astype(dtype)


def forward(
    cfg: ModelConfig,
    params: PyTree,
    batch: dict[str, jax.Array],
    cache: PyTree | None = None,
    return_cache: bool = False,
    *,
    adapters: PyTree | None = None,
    lora_scale: float = 1.0,
    routed: bool = False,
):
    """Returns (logits, moe_aux_loss, new_cache), and with ``routed`` also
    the expert layers' counts summed over them (``layers.apply_moe_ffn``'s):
    the (B, S) count of each position's (token, expert) pairs they computed
    (``pairs``), and their calls, the calls that ran the smallest pair
    buffer and the buffers' rows.

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,D)} for frontend-stub
    archs. Decode mode iff ``cache`` is not None (then S == 1 and the new
    token goes to buffer slot ``cache["len"]``). ``return_cache=True`` in
    full-sequence mode collects prefill caches (exact-length buffers).

    ``adapters`` adds LoRA factors on activations (``layers.lora``, scaled
    by ``lora_scale``), never merged into the weights: ``prefix`` (a list of
    per-layer attention adapters, None for a layer without), ``blocks``
    (per-slot adapters stacked over periods, like ``params["blocks"]``) and
    ``head`` (on the logits); a leading client axis on the factors makes the
    batch client-major (``layers.lora``). Full-sequence mode only.
    """
    decode = cache is not None
    collect = decode or return_cache
    pos0 = cache["len"] if decode else 0
    adapters = adapters or {}
    if "tokens" in batch:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    else:
        x = batch["embeds"]
    if cfg.query_pre_attn_scalar is not None:  # gemma scales embeddings
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.is_encoder:
        x = x + _sinusoidal(x.shape[1], cfg.d_model, x.dtype)[None]

    aux_total = jnp.zeros((), jnp.float32)
    routed_total = L.no_routing(x.shape[:2])
    new_cache: dict[str, Any] = {"len": pos0 + x.shape[1]} if collect else None

    prefix_ad = adapters.get("prefix") or [None] * len(cfg.prefix)
    for i, spec in enumerate(cfg.prefix):
        c_i = cache["prefix"][i] if decode else None
        x, nc, (aux, r) = _apply_layer(
            params["prefix"][i], spec, cfg, x, cache=c_i, pos0=pos0, decode=decode, collect=collect,
            adapter=prefix_ad[i], lora_scale=lora_scale,
        )
        aux_total += aux
        routed_total = jax.tree_util.tree_map(jnp.add, routed_total, r)
        if collect:
            new_cache.setdefault("prefix", []).append(nc)

    if cfg.num_periods:
        block_ad = adapters.get("blocks")

        def period_fn(carry, xs):
            x_c, aux_c, routed_c = carry
            lp, lc, la = xs
            ncs = {}
            for i, spec in enumerate(cfg.pattern):
                x_c, nc, (aux, r) = _apply_layer(
                    lp[f"slot{i}"], spec, cfg, x_c,
                    cache=lc.get(f"slot{i}"), pos0=pos0, decode=decode, collect=collect,
                    adapter=la.get(f"slot{i}"), lora_scale=lora_scale,
                )
                aux_c += aux
                routed_c = jax.tree_util.tree_map(jnp.add, routed_c, r)
                ncs[f"slot{i}"] = nc if nc is not None else 0
            return (x_c, aux_c, routed_c), (ncs if collect else 0)

        body = period_fn
        if cfg.train.remat and not decode and not collect:
            body = jax.checkpoint(period_fn, prevent_cse=False)
        xs = (params["blocks"], cache["blocks"] if decode else {}, block_ad or {})
        (x, aux_total, routed_total), ys = jax.lax.scan(body, (x, aux_total, routed_total), xs)
        if collect:
            new_cache["blocks"] = ys

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L._dot("bsd,vd->bsv", x, params["embed"])
    else:
        logits = L._dot("bsd,dv->bsv", x, params["lm_head"])
    if "head" in adapters:
        logits = logits + L.lora(x, adapters["head"], lora_scale).astype(logits.dtype)
    if cfg.final_logit_softcap is not None:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    if routed:
        return logits, aux_total, new_cache, routed_total
    return logits, aux_total, new_cache
