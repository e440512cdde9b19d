"""One chip's share of an expert-parallel MoE layer, the DeepSeek-V2 gate, and
YaRN rope scaling (``models/layers.py``).

* The shares of eight chips, each holding an eighth of the routed experts,
  with the shared experts (which every chip computes alike) counted once,
  add up to the uncut layer — also for tokens past a 4,096-token group.
* The pair buffer runs the smallest rung of its ladder that holds the held
  pairs, and computes what the one full-size buffer computed.
* Without ``norm_topk_prob`` the gate weights the top-k experts by their raw
  softmax probabilities.
* YaRN's frequencies and softmax scale follow DeepSeek-V2's closed form.
"""
import dataclasses
import math

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MLASpec, MoESpec, get_config
from repro.configs.base import reduced_config
from repro.models import layers as L


def _moe_cfg(E=64, K=6, d=32, de=16, shared=2, norm=False):
    cfg = reduced_config(get_config("deepseek-v2-lite-16b"), d_model=d)
    moe = MoESpec(num_experts=E, top_k=K, d_expert=de, num_shared=shared, norm_topk_prob=norm)
    return dataclasses.replace(cfg, moe=moe, moe_dropless=False)


def _share(params, first, held):
    return {**params, **{k: params[k][first:first + held] for k in ("wg", "wu", "wd")}}


@pytest.mark.parametrize("B,S", [(2, 16), (2, 2100)])
def test_eight_shares_add_up_to_the_uncut_layer(B, S):
    cfg = _moe_cfg(d=16, de=8)
    K = cfg.moe.top_k
    uncut_cfg = dataclasses.replace(cfg, moe_dropless=True)  # every expert held
    params = L.init_moe_ffn(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = L.apply_moe_held(params, x, uncut_cfg)
        shared = L.apply_dense_ffn(params["shared"], x)
        total, routed = -7 * shared, 0
        for s in range(8):
            moe = dataclasses.replace(cfg.moe, held_first=8 * s, held=8)
            part_cfg = dataclasses.replace(cfg, moe=moe, moe_dropless=True)
            out, _, r = L.apply_moe_held(_share(params, 8 * s, 8), x, part_cfg)
            total, routed = total + out, routed + r["pairs"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(routed) == K)  # each token's top-k pairs, each on one share
    assert float(jnp.min(jnp.max(jnp.abs(uncut - shared), axis=-1))) > 0  # no token left without its experts


@pytest.mark.parametrize("T,group", [(120, 64), (128, 64)])
def test_all_experts_held_is_the_capacity_layer_with_room(T, group):
    """Capacity dispatch with room for every pair is an independent
    implementation of the same routing; its last group is padded, and the
    tokens in it are computed, not zeroed."""
    cfg = _moe_cfg(E=8, K=2)
    params = L.init_moe_ffn(jax.random.PRNGKey(2), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        held, _, routed = L.apply_moe_ffn(params, x, dataclasses.replace(cfg, moe_dropless=True))
        uncut, _, kept = L.apply_moe_ffn(params, x, cfg, capacity_factor=4.0, group_size=group)
    np.testing.assert_allclose(np.asarray(held), np.asarray(uncut), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(routed["pairs"]) == 2) and np.all(np.asarray(kept["pairs"]) == 2)


def _full_buffer(params, x, cfg):
    """The held layer with one pair buffer of every token's
    ``min(top_k, held)`` slots, ``T * min(top_k, held)`` rows, as it ran
    before the ladder."""
    moe = cfg.moe
    B, S, D = x.shape
    E, K, Eh, first = moe.num_experts, moe.top_k, moe.num_held, moe.held_first
    xt = x.reshape(B * S, D)
    T = xt.shape[0]
    probs, topw, topi = L._gate(params, xt, moe)
    local = topi - first
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh).reshape(-1)
    n = T * min(K, Eh)
    order = jnp.argsort(key, stable=True)[:n]
    tok = order // K
    live = key[order] < Eh
    w = jnp.where(live, topw.reshape(-1)[order], 0.0)
    sizes = jnp.sum(jax.nn.one_hot(key, Eh, dtype=jnp.int32), axis=0)
    xs = jnp.where(live[:, None], xt[tok], 0.0)
    h = jax.nn.silu(L._grouped(xs, params["wg"], sizes)) * L._grouped(xs, params["wu"], sizes)
    h = jnp.where(live[:, None], h, 0.0)
    y = L._grouped(h, params["wd"], sizes)
    y = jnp.where(live[:, None], y * w[:, None].astype(y.dtype), 0.0)
    out = jnp.zeros((T, D), y.dtype).at[tok].add(y).reshape(B, S, D)
    if moe.num_shared:
        out = out + L.apply_dense_ffn(params["shared"], x)
    routed = jnp.sum(held, axis=-1, dtype=jnp.int32).reshape(B, S)
    return out, L._balance_loss(probs, topi, E, K), routed


@pytest.mark.parametrize("T,E,K,held,ladder", [
    (8192, 64, 6, 8, (12288, 24576, 49152)),  # a train block of the DeepSeek cell
    (1024, 64, 6, 8, (1536, 3072, 6144)),
    (256, 64, 6, 8, (384, 768, 1536)),
    (100, 64, 6, 8, (256, 384, 600)),  # rounded up to the row tile
    (40, 64, 6, 8, (128, 240)),  # a quarter and a half round to one rung
    (16, 64, 6, 8, (96,)),  # a rung rounds up past the whole buffer
    (4096, 64, 6, 16, (12288, 24576)),  # a quarter is below twice the expected pairs
    (4096, 64, 6, 32, (24576,)),
    (4096, 8, 2, None, (8192,)),  # every expert held: every pair is live
])
def test_pair_ladder(T, E, K, held, ladder):
    moe = MoESpec(num_experts=E, top_k=K, d_expert=16, held=held)
    assert L.pair_ladder(T, moe) == ladder


def _laddered_and_full(dtype, steer, held=8):
    """The laddered layer and :func:`_full_buffer`: outputs, losses, counts
    and input gradients, for a router steered by ``steer`` towards the
    held experts (the tokens' features have a positive mean, so that
    tilting the held experts' router columns raises their logits for
    nearly every token)."""
    cfg = _moe_cfg(E=64, K=6)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=held), moe_dropless=True)
    params = L.init_moe_ffn(jax.random.PRNGKey(7), cfg, dtype)
    params["router"] = params["router"].at[:, :cfg.moe.num_held].add(steer)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 128, cfg.d_model), jnp.float32) + 1.0
    probe = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def run(layer):
        def loss(x):
            out, aux, routed = layer(params, x, cfg)
            return jnp.sum(out * probe) + aux, (out, aux, routed)
        (_, found), grad = jax.value_and_grad(loss, has_aux=True)(x)
        return found, grad

    return cfg, run(L.apply_moe_held), run(_full_buffer)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("steer,rung", [(0.0, 0), (0.05, 1), (0.1, 2), (1.0, 2)])
def test_laddered_pairs_are_the_full_buffer(dtype, steer, rung):
    """Unsteered, the held pairs fit the smallest rung; a router steered
    towards the held experts fills the middle rung, then the top rung and
    at last every slot of it. Whichever rung runs, no pair is dropped: the outputs and input
    gradients are the full buffer's to float32 rounding (the conditional
    lets the compiler order the sums of each token's gradient, and the
    products of a shorter buffer, otherwise), the balance loss and the
    routed pairs exactly."""
    cfg, ((out, aux, counts), grad), ((ref, ref_aux, routed), ref_grad) = _laddered_and_full(dtype, steer)
    ladder = L.pair_ladder(256, cfg.moe)
    assert len(ladder) == 3
    pairs = int(jnp.sum(routed))
    assert (ladder[rung - 1] if rung else 0) < pairs <= ladder[rung]
    assert {k: int(counts[k]) for k in ("calls", "small", "rows")} == {
        "calls": 1, "small": int(rung == 0), "rows": ladder[rung]}
    np.testing.assert_array_equal(np.asarray(counts["pairs"]), np.asarray(routed))
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(ref_aux))
    for a, b in ((out, ref), (grad, ref_grad)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0, atol=1e-6 * np.abs(b).max())


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_every_expert_held_runs_one_buffer_without_a_conditional():
    """With every expert held every pair is live: the ladder is the one full
    buffer, and the layer is the full buffer's program, gradients and all."""
    cfg, ((out, aux, counts), grad), ((ref, ref_aux, routed), ref_grad) = _laddered_and_full(
        jnp.float32, 0.0, held=None)
    assert L.pair_ladder(256, cfg.moe) == (256 * 6,)
    for a, b in ((out, ref), (aux, ref_aux), (counts["pairs"], routed), (grad, ref_grad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(counts["small"]) == 1 and int(counts["rows"]) == 256 * 6
    params = L.init_moe_ffn(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jnp.zeros((2, 128, cfg.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(L.apply_moe_held(params, x, cfg)[0])))(x)
    assert not [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]


def _by_hand(params, x, moe, renorm):
    """The layer for one token, written out in NumPy float64."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items() if k != "shared"}
    sh = {k: np.asarray(v, np.float64) for k, v in params["shared"].items()}
    xv = np.asarray(x, np.float64)
    z = xv @ p["router"]
    probs = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
    top = np.argsort(-probs)[:moe.top_k]
    w = probs[top] / probs[top].sum() if renorm else probs[top]

    def swiglu(wg, wu, wd):
        g = xv @ wg
        return (g / (1 + np.exp(-g)) * (xv @ wu)) @ wd

    out = sum(wk * swiglu(p["wg"][e], p["wu"][e], p["wd"][e]) for wk, e in zip(w, top))
    return out + swiglu(sh["wg"], sh["wu"], sh["wd"]), w


@pytest.mark.parametrize("norm", [False, True])
def test_gate_weights_raw_softmax_unless_renormalized(norm):
    cfg = dataclasses.replace(_moe_cfg(E=8, K=3, norm=norm), moe_dropless=True)
    params = L.init_moe_ffn(jax.random.PRNGKey(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 1, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, _ = L.apply_moe_held(params, x, cfg)
    want, w = _by_hand(params, x[0, 0], cfg.moe, renorm=norm)
    np.testing.assert_allclose(np.asarray(out[0, 0], np.float64), want, rtol=1e-4, atol=1e-5)
    assert (abs(w.sum() - 1) < 1e-9) == norm  # raw softmax weights do not sum to one


def test_yarn_matches_the_closed_form():
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    assert L.yarn_range(64, cfg.rope_theta, y) == (10, 23)
    i = np.arange(32, dtype=np.float64)
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(np.asarray(L.rope_inv_freq(64, cfg.rope_theta, y)), want, rtol=1e-6)
    g = 0.1 * 0.707 * math.log(40) + 1
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * g * g)
    assert L.mla_softmax_scale(cfg) == pytest.approx(0.1147, abs=5e-5)
    # cos and sin are scaled by g(mscale) / g(mscale_all_dim) = 1: rope keeps norms
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 2, 64), jnp.float32)
    r = L.rope(x, jnp.arange(8), cfg.rope_theta, y)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1), np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # without scaling, the frequencies are the plain ones
    np.testing.assert_allclose(np.asarray(L.rope_inv_freq(64, 10000.0)), extra, rtol=1e-6)


def test_mla_uses_the_yarn_softmax_scale():
    base = reduced_config(get_config("deepseek-v2-lite-16b"))
    plain = dataclasses.replace(base, rope_scaling=None)
    assert L.mla_softmax_scale(plain) == pytest.approx((base.mla.qk_nope_head_dim + base.mla.qk_rope_head_dim) ** -0.5)
    assert L.mla_softmax_scale(base) > L.mla_softmax_scale(plain)
    assert isinstance(base.mla, MLASpec)
