"""The MLA + MoE client model (``models/mla_moe_lora.py``) through the seam at a
reduced size on the CPU: the same module and reference as the
``deepseek-v2-lite-ep8`` configuration, over a small registered program
config of the same kind (one dense layer, expert layers holding 8 of 16
experts, YaRN, latent attention). The byte ledger is exact at the module's
row, the program's training lands within ``train_gap``'s limit of the
reference and the bf16 control beyond it, two seeds meet one trail, and
the module refuses widths that are not the program's."""
from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from chipbench import harness, spec
from chipbench.testing import SEED, fixed_window, run_small, small_cell

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CONFIG = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text())
PROGRAM = "deepseek-v2-lite-reduced-test"
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, vocab_size=512, num_hidden_layers=4)


@pytest.fixture(scope="module")
def program():
    """A small program config of DeepSeek-V2-Lite's kind, registered for
    the module's tests and taken out after."""
    from repro.configs import ARCH_REGISTRY, MLASpec, get_config, register_arch

    ds = get_config(CONFIG["program_config"])
    cfg = dataclasses.replace(
        ds, name=PROGRAM, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=512,
        num_periods=3, moe=dataclasses.replace(ds.moe, num_experts=16, top_k=4, d_expert=32),
        mla=MLASpec(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8),
    )
    register_arch(cfg)
    yield cfg
    ARCH_REGISTRY.pop(PROGRAM)


# train_gap's limit at this size, from CPU readings: the program <= 0.0097
# (a top-4 near-tie flipping a token's expert; 2e-7 without one), the bf16
# control >= 0.06
SMALL_LIMITS = {**CONFIG["limits"], "train_gap": 0.03}


def _config(**over) -> dict:
    return {**CONFIG, **SMALL, "name": "ds_small", "program_config": PROGRAM, "num_periods": 2,
            "experts_held": 8, "vocab_held": 256, "lora_rank": 4, "n_train": 4, "n_test": 2, "seq_len": 16,
            "base_round_time_s": 5.0, "limits": SMALL_LIMITS, **over}


def _cell(tmp_path, config: dict, clients: int = 16):
    """The benchmark copied under ``tmp_path`` with ``config`` and a cell of
    it under the w5 traffic."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "out"))
    (bench_dir / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config["name"], "source": config["source"],
                             "file": f"benchmarks/chip/configs/{config['name']}.json", "reduced": [], "why": "test"})
    name = f"{config['name']}-w5"
    bench["workloads"].append({"name": name, "config": config["name"], "traffic": "w5", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return small_cell(name, clients=clients, horizon=40.0, bench_dir=bench_dir, root=tmp_path)


def test_the_small_config_states_the_program(program):
    model = spec.client_model("mla_moe_lora")
    model.check_sizes(_config())
    assert model.row_floats(_config()) == (64 * 4 + 4 * 256 + 3 * (64 * 4 + 4 * 4 * 16)
                                           + 3 * (64 * 4 + 4 * (16 + 8)))


def test_the_program_reads_the_benchmarks_base_as_the_reference_does(program):
    """The base the module draws (bf16, the reference's layout), handed to
    the program in its layout, gives the program's forward the reference's
    loss for a delta with every factor nonzero."""
    import jax
    import numpy as np

    from repro.fl.lm_task import make_lm_data

    model = spec.client_model("mla_moe_lora")
    config = _config()
    base = model.draw_base(config, 3)
    assert all(a.dtype.name == "bfloat16" for a in jax.tree_util.tree_leaves(base))
    task = model._program_task(config, base)
    delta = task.init_params(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), len(jax.tree_util.tree_leaves(delta))))
    delta = jax.tree_util.tree_map(lambda a: 0.3 * jax.random.normal(next(keys), a.shape), delta)
    data = make_lm_data(1, vocab_size=config["vocab_held"], n_train=2, n_test=1, seq_len=16, seed=0)[0]
    ref = spec.load_module(BENCH / config["reference"])
    with jax.default_matmul_precision("highest"):
        mine, _ = task._nll(task.operands, jax.tree_util.tree_map(lambda a: a[None], delta),
                            data.tokens_train[None], data.labels_train[None], np.ones((1, 2), np.float32))
        want = ref.forward_nll(model._reference_delta(config, harness.flat(delta)), jax.device_put(base),
                               data.tokens_train, data.labels_train, model._sizes(config))
    assert float(mine[0]) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("key,value", [("hidden_size", 32), ("kv_lora_rank", 8), ("moe_intermediate_size", 16),
                                       ("num_experts_per_tok", 2), ("norm_topk_prob", True)])
def test_check_sizes_refuses_a_width_not_the_programs(program, key, value):
    with pytest.raises(ValueError, match=key):
        spec.client_model("mla_moe_lora").check_sizes(_config(**{key: value}))


def test_the_published_config_is_the_programs():
    model = spec.client_model("mla_moe_lora")
    model.check_sizes(CONFIG)
    assert model.row_floats(CONFIG) == CONFIG["row_floats"] == 428544
    assert model.train_flops_per_upload(CONFIG) == pytest.approx(2.14e12, rel=0.01)
    with pytest.raises(ValueError, match="qk_rope_head_dim"):
        model.check_sizes({**CONFIG, "qk_rope_head_dim": 32})


def test_through_the_seam_with_reference_and_control(program, tmp_path):
    cell = _cell(tmp_path, _config())
    with fixed_window(6):
        out = run_small(cell, control=True)
    res, ctl = out["result"], out["control"]
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["ledger_bytes_off"]["value"] == 0 and checks["assign_miss"]["value"] == 0
    assert 0 <= checks["train_gap"]["value"] <= checks["train_gap"]["limit"]
    assert ctl["numbers"]["train_gap"]["value"] > checks["train_gap"]["limit"], ctl["numbers"]
    assert not ctl["correct"]


def test_two_seeds_meet_one_trail(program, tmp_path):
    cell = _cell(tmp_path, _config(), clients=8)
    trails = []
    with fixed_window(3):
        for seed in (SEED, 2**31 + 11):
            seen = {}

            def readings(cell, seed, rec, data, sim):
                seen["rec"] = rec
                return {}

            out = harness.run(cell, seed, 1.0, trace_dir=None, t_process=0.0, check=readings)
            trails.append(out["info"]["warmup_trail"])
            rec = seen["rec"]
            row = cell.model.row_floats(cell.config)
            assert all(harness.flat(t).size == row for _, _, t, *_ in rec.samples.train)
    assert trails[0] == trails[1]


@pytest.mark.parametrize("op_name,scope,hit", [
    ("jit(_train_launch_bank)/while/body/moe/dot_general", "moe", True),
    ("jit(f)/transpose(jvp(moe))/ragged_dot", "moe", True),
    ("jit(f)/checkpoint/rematted_computation/mla/add", "mla", True),
    ("jit(f)/moe_gate/add", "moe", False),
    ("jit(f)/mla/dot_general", "moe", False),
])
def test_a_scope_is_found_in_an_op_name(op_name, scope, hit):
    from chipbench.scopes import names_scope

    assert names_scope(op_name, scope) is hit


@pytest.mark.parametrize("event,name", [
    ("%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128]{1,0} %p), kind=kLoop", "fusion.12"),
    ("%copy-start.6 = (f32[2048,8]{0,1}, u32[]) copy-start(f32[2048,8]{0,1} %x)", "copy-start.6"),
    ("wrapped_sine", "wrapped_sine"),
])
def test_an_op_event_names_its_instruction(event, name):
    from chipbench.scopes import instruction

    assert instruction(event) == name


def test_nested_operations_count_once():
    from chipbench.scopes import _union_ns

    assert _union_ns([(0, 10), (2, 5), (8, 12), (20, 21)]) == 13


def test_a_traces_hlo_gives_each_instruction_its_op_name(tmp_path):
    """The program's HLO in the trace's metadata plane maps the instruction
    names that operation events carry to the ``op_name`` their named scopes
    wrote."""
    import jax
    import jax.numpy as jnp

    from chipbench import program_spans, scopes

    @jax.jit
    def f(x):
        with jax.named_scope("moe"):
            y = jnp.sin(x) @ x
        with jax.named_scope("mla"):
            y = jnp.cos(y) @ x
        return y.sum()

    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    names = scopes.op_names(program_spans.newest(str(tmp_path)))
    (program,) = [k for k in names if k.startswith("jit_f(")]
    for scope in ("moe", "mla"):
        assert any(scopes.names_scope(o, scope) for o in names[program].values()), names[program]


def test_moe_cost_counts_pairs_tokens_and_weights():
    model = spec.client_model("mla_moe_lora")
    ops, nbytes = model.moe_cost(CONFIG, pairs=1000, tokens=200, launches=3)
    per = 2 * 3 * 2048 * 1408 * 2
    assert ops == 1000 * per + 200 * 4 * 2 * per  # shared experts in each of the 4 expert layers
    assert nbytes == 3 * 4 * (8 + 2) * 3 * 2048 * 1408 * 2
