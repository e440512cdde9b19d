"""Compile every Pallas kernel of the main path for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at the widths the main path uses —
har's 4,550-float plane row with 2..64 centers and up to a 4,096-client
fleet, the tiny_lm attention shapes under the fleet's client vmap, and one
bf16 S=1024, hd=128 attention shape. The chip's compiler refuses what
interpret mode accepts (unaligned tiles, scalar stores to VMEM, primitives
Mosaic cannot lower), so these compiles guard the chip path on CPU.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU compiler's library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.assign_lerp import assign_and_lerp
from repro.kernels.chi2_feedback import chi2_feedback, chi2_feedback_segmented
from repro.kernels.l1_distance import l1_distance
from repro.kernels.l1_pairwise import l1_distance_pairwise
from repro.kernels.merge_attention import merge_attention

N = 4550  # har plane row: 64*64 + 64 + 64*6 + 6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip, with the
    persistent compilation cache off: entries written for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [2, 16, 64])
def test_l1_distance(spec, c):
    _compile(l1_distance, spec((N,)), spec((c, N)))


@pytest.mark.parametrize("c", [4, 64])
def test_assign_and_lerp(spec, c):
    _compile(lambda u, cs: assign_and_lerp(u, cs, 0.25), spec((N,)), spec((c, N)))


@pytest.mark.parametrize("m,c", [(64, 16), (1024, 1024), (4096, 64)])
def test_l1_distance_pairwise(spec, m, c):
    _compile(l1_distance_pairwise, spec((m, N)), spec((c, N)))


def test_merge_attention(spec):
    _compile(merge_attention, spec((N,)), spec((N,)), spec((N,)))


def test_chi2_feedback(spec):
    _compile(chi2_feedback, *(spec((4096, 6)),) * 3)


def test_chi2_feedback_segmented(spec):
    _compile(chi2_feedback_segmented, *(spec((4096, 6)),) * 3, spec((4096, 64)))


def test_ingest_chain(spec, monkeypatch):
    """The coalesced ingest scan with the Pallas L1 body, as a TPU backend
    dispatches it (the CPU backend here would pick the oracle)."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    chain = jax.jit(ops._ingest_chain_jit.__wrapped__,
                    static_argnames=("beta", "switch_margin", "with_stats"))
    S, C = 64, 16
    args = (spec((S, N)), spec((C, N)), spec((C, N)), spec((), jnp.int32),
            spec((S,), jnp.int32), spec((S,), jnp.int32), spec((S,), jnp.bool_))
    compiled = chain.lower(*args, beta=0.25, switch_margin=0.1, with_stats=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


# B, H, KV, S, hd, dtype, client vmap width
ATTN = [(8, 4, 2, 32, 16, jnp.float32, 16), (2, 8, 8, 1024, 128, jnp.bfloat16, None)]


@pytest.mark.parametrize("case", ATTN, ids=["tiny_lm-vmap16", "bf16-S1024-hd128"])
def test_flash_attention_fwd_bwd(spec, case):
    """Forward with lse plus both backward kernels, through the custom_vjp
    the fleet's train step differentiates."""
    B, H, KV, S, hd, dtype, clients = case
    lead = (clients,) if clients else ()
    q, kv = spec(lead + (B, H, S, hd), dtype), spec(lead + (B, KV, S, hd), dtype)

    def attn(q, k, v):  # causal, scale, window, softcap, q_pos0, interpret
        return ops._attention_trainable(q, k, v, True, None, None, None, 0, False)

    if clients:
        attn = jax.vmap(attn)
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)



def test_expert_share_fwd_bwd_at_deepseek_widths(spec):
    """DeepSeek-V2-Lite's expert layer as one chip of eight holds it (8 of 64
    routed experts of width 1,408, 2 shared, hidden 2,048), 1,024 tokens,
    bf16 weights under float32 activations and the configurations'
    ``highest`` matmul precision: its grouped products, forward and input
    gradient (a bf16 grouped product asked for more than one pass is
    refused by the chip's compiler)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=8), moe_dropless=True)
    params = jax.eval_shape(lambda: L.init_moe_ffn(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    params = jax.tree_util.tree_map(lambda p: spec(p.shape, p.dtype), params)
    loss = lambda x, p: jnp.sum(L.apply_moe_held(p, x, cfg)[0])  # noqa: E731
    with jax.default_matmul_precision("highest"):
        _compile(jax.grad(loss), spec((4, 256, cfg.d_model)), params)
