"""Launch layer: input specs, sharding construction, the loop-aware HLO cost
model, and a 1-device end-to-end lower+compile of a reduced cell."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_REGISTRY, SHAPES
from repro.configs.base import reduced_config
from repro.launch import specs as SP
from repro.launch.dryrun import parse_collective_bytes
from repro.launch.hlo_cost import HloCostModel, analyze
from repro.launch.mesh import batch_axes, make_smoke_mesh
from repro.launch.shardings import batch_shardings, param_shardings


def test_input_specs_are_abstract():
    cfg = ARCH_REGISTRY["llama3-405b"]  # 405B: would OOM if actually allocated
    spec = SP.input_specs(cfg, SHAPES["train_4k"])
    for leaf in jax.tree_util.tree_leaves(spec):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert spec["batch"]["tokens"].shape == (256, 4096)


def test_embeds_input_archs_get_embedding_specs():
    for name in ("pixtral-12b", "hubert-xlarge"):
        cfg = ARCH_REGISTRY[name]
        spec = SP.input_specs(cfg, SHAPES["train_4k"])
        assert "embeds" in spec["batch"]
        assert spec["batch"]["embeds"].shape[-1] == cfg.d_model


def test_param_count_specs_match_analytic():
    """eval_shape param count within 2% of the analytic formula (catches
    drift between config math and actual model structure)."""
    for name in ("llama3.2-1b", "gemma2-2b", "command-r-35b"):
        cfg = ARCH_REGISTRY[name]
        exact = SP.model_param_count(cfg)
        analytic = cfg.param_count()
        assert abs(exact - analytic) / exact < 0.02, name


def test_effective_microbatches_divisibility():
    cfg = ARCH_REGISTRY["llama3-405b"]
    shape = SHAPES["train_4k"]  # global_batch 256
    import dataclasses

    for want, dp in [(8, 16), (7, 16), (1, 256), (3, 8)]:
        cfg2 = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, microbatches=want))
        n = SP.effective_microbatches(cfg2, shape, dp)
        assert shape.global_batch % n == 0
        assert (shape.global_batch // n) % dp == 0
        assert n <= max(want, 1)


def test_smoke_mesh_cell_compiles():
    """Reduced config through the *production* sharding path on the 1-device
    mesh: in_shardings with named axes must lower + compile."""
    from repro.models.steps import make_train_step

    cfg = reduced_config(ARCH_REGISTRY["llama3.2-1b"])
    mesh = make_smoke_mesh()
    state = SP.state_specs(cfg, jnp.float32)
    batch = {
        "tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32),
    }
    state_sh = state._replace(
        params=param_shardings(cfg, mesh, state.params),
        opt_state=param_shardings(cfg, mesh, state.opt_state),
        step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    import dataclasses

    shape = dataclasses.replace(SHAPES["train_4k"], global_batch=4, seq_len=16)
    batch_sh = batch_shardings(cfg, shape, mesh, batch)
    with mesh:
        lowered = jax.jit(make_train_step(cfg), in_shardings=(state_sh, batch_sh)).lower(
            state, batch
        )
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_batch_axes():
    assert batch_axes(make_smoke_mesh()) == ("data",)


# ------------------------------------------------------------- HLO cost model
def test_hlo_cost_matches_xla_on_scan_free_program():
    """On a program with no while loops, the loop-aware model should be in
    the same ballpark as XLA's own cost_analysis for flops."""

    def f(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per device
        cost = cost[0]
    xla_flops = cost["flops"]
    got = analyze(compiled.as_text())
    assert got["flops"] >= 2 * 64 * 128 * 32  # at least the matmul
    assert got["flops"] <= max(xla_flops * 1.5, got["flops"])  # same ballpark


def test_hlo_cost_multiplies_scan_trips():
    """A scanned matmul must count body FLOPs x trip count — the whole point
    of the loop-aware model (XLA counts the body once)."""

    def f(x, ws):
        def body(carry, w):
            return jnp.tanh(carry @ w), 0

        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jnp.zeros((32, 32), jnp.float32)
    ws = jnp.zeros((20, 32, 32), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    got = analyze(compiled.as_text())
    one_layer = 2 * 32 * 32 * 32
    assert got["flops"] >= 20 * one_layer * 0.9, got["flops"]


def test_parse_collective_bytes_on_synthetic_hlo():
    hlo = """
HloModule test
ENTRY %main (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  %ag = f32[512]{0} all-gather(%p0), replica_groups={}, dimensions={0}
  %ar = f32[128]{0} all-reduce(%p0), to_apply=%add
  %done = f32[128]{0} copy(%ar)
  ROOT %out = f32[128]{0} add(%done, %p0)
}
"""
    out = parse_collective_bytes(hlo)
    assert out["all-gather"] == 512 * 4
    assert out["all-reduce"] == 128 * 4
    assert out["count"] == 2


def test_hlo_cost_collectives_bucketed():
    mesh = make_smoke_mesh()

    def f(x):
        return jax.lax.psum(x, "data")

    from jax import shard_map

    x = jnp.ones((4, 8), jnp.float32)
    sm = shard_map(
        f, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("data"),
        out_specs=jax.sharding.PartitionSpec("data"),
    )
    compiled = jax.jit(sm).lower(x).compile()
    got = analyze(compiled.as_text())
    assert isinstance(got["collectives"], dict)
