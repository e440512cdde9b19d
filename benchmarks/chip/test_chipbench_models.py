"""A configuration brings its client model as a module of its own: a copy of
the MLP's module, named by a new configuration, is found as new files and
replays the har cell exactly; the program's LM task runs through the same
seam with an exact byte ledger at its delta width; the MLP's module refuses
widths that are not the program's."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import pytest

from chipbench import check, harness, spec
from chipbench.testing import SEED, fixed_window, run_small, small_cell

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = BENCHMARK["workloads"][0]


def _copy(tmp_path, config: dict, model_source: str):
    """The benchmark copied under ``tmp_path`` with one more configuration,
    its client model and a cell of it under the har cell's traffic; returns
    the copy's directory and the new cell's name."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "out"))
    (bench_dir / "models" / f"{config['client_model']}.py").write_text(model_source)
    (bench_dir / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": config["name"], "source": config["source"],
                             "file": f"benchmarks/chip/configs/{config['name']}.json",
                             "reduced": [], "why": "a copy"})
    cell = f"{config['name']}-{CELL['traffic']}"
    bench["workloads"].append({"name": cell, "config": config["name"], "traffic": CELL["traffic"],
                               "chips": 1, "why": "a copy"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench_dir, cell


def test_a_new_client_model_is_found_as_new_files(tmp_path):
    har = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())
    config = {**har, "name": "har_copy", "client_model": "mlp_copy"}
    bench_dir, name = _copy(tmp_path, config, (BENCH / "models" / "mlp.py").read_text())
    copy = small_cell(name, clients=32, bench_dir=bench_dir, root=tmp_path)
    assert Path(copy.model.__file__) == bench_dir / "models" / "mlp_copy.py"
    with fixed_window(8):
        theirs = run_small(copy)
        ours = run_small(small_cell(CELL["name"], clients=32))
    assert ours["result"]["correct"], ours["result"]["checks"]
    assert theirs["result"]["checks"] == ours["result"]["checks"]
    assert theirs["info"]["warmup_trail"] == ours["info"]["warmup_trail"]
    assert theirs["info"]["window"]["uploads"] == ours["info"]["window"]["uploads"] > 0


def test_mlp_refuses_widths_not_the_programs():
    mlp = spec.client_model("mlp")
    har = json.loads((BENCH / "configs" / "har.json").read_text())
    mlp.check_sizes(har)
    with pytest.raises(ValueError):
        mlp.check_sizes({**har, "hidden": [32]})


LM_MODEL = '''"""Client model for a test: the program's LM task on ``tiny_lm``. The row
is the flattened LoRA and head delta; it has no training reference."""


def _task():
    from repro.fl.lm_task import default_lm_task

    return default_lm_task()


def check_sizes(config):
    cfg = _task().cfg
    program = {"d_model": cfg.d_model, "vocab": cfg.padded_vocab, "num_periods": cfg.num_periods,
               "q_width": cfg.num_heads * cfg.resolved_head_dim, "lora_rank": _task().lora_rank,
               "attn_slots": sum(s.mixer in ("attn", "attn_local") for s in cfg.pattern)}
    if {k: config[k] for k in program} != program:
        raise ValueError(f"config sizes differ from the program's {program}")


def draw(config, rng, program_seed, seed):
    import jax

    from repro.fl.lm_task import make_lm_data

    check_sizes(config)
    task = _task()
    data = make_lm_data(config["num_clients"], vocab_size=task.cfg.vocab_size,
                        latent_clusters=config["latent_clusters"], n_train=config["n_train"],
                        n_test=config["n_test"], seq_len=config["seq_len"],
                        seed=int(rng.integers(1 << 30)))
    init = task.init_params(jax.random.PRNGKey(program_seed))
    client = dict(num_classes=task.buckets, task=task, local_epochs=config["local_epochs"],
                  lr=config["lr"])
    return data, init, client


def row_floats(config):
    d, r = config["d_model"], config["lora_rank"]
    slot = config["num_periods"] * (d * r + r * config["q_width"])
    return d * r + r * config["vocab"] + config["attn_slots"] * slot


def train_flops_per_upload(config, *, head_only=False):
    tokens = config["local_epochs"] * config["n_train"] * config["seq_len"]
    return tokens * (4 if head_only else 6) * row_floats(config)


def train_reference(config, base, data, *, epochs, lr, head_only, cast=None):
    raise NotImplementedError("no LM training reference")
'''


def test_the_program_lm_task_runs_through_the_seam(tmp_path):
    har = json.loads((BENCH / "configs" / f"{CELL['config']}.json").read_text())
    config = {k: v for k, v in har.items()
              if k not in ("task", "input_dim", "hidden", "num_classes", "row_floats", "samples_per_client")}
    config.update(name="tiny_lm_delta", client_model="tiny_lm_delta", reference="none",
                  d_model=64, vocab=256, num_periods=2, q_width=64, lora_rank=4, attn_slots=1,
                  n_train=8, n_test=4, seq_len=32, local_epochs=2, lr=0.5, base_round_time_s=5.0)
    bench_dir, name = _copy(tmp_path, config, LM_MODEL)
    cell = small_cell(name, clients=16, horizon=40.0, bench_dir=bench_dir, root=tmp_path)
    seen = {}

    def readings(cell, seed, rec, data, sim):
        seen["rec"] = rec
        return {**check.ingest_readings(cell, rec), **check.ledger_readings(cell, rec, sim)}

    with fixed_window(8):
        r = harness.run(cell, SEED, 1.0, trace_dir=None, t_process=time.perf_counter(), check=readings)["check"]
    rec, model = seen["rec"], cell.model
    row = model.row_floats(cell.config)
    assert row == 64 * 4 + 4 * 256 + 2 * (64 * 4 + 4 * 64)  # head a and b, the query LoRA of both periods
    assert all(harness.flat(trained).size == row for _, _, trained, *_ in rec.samples.train)
    assert r["ledger_bytes_off"][0] == 0
    assert r["assign_miss"][0] == 0 and r["assign_miss"][1] > 0
    assert r["ingest_gap"][1] > 0 and r["ingest_gap"][0] <= 1e-5
    assert rec.window_trained == len(rec.samples.train) > 0
    assert rec.window_flops == sum(model.train_flops_per_upload(cell.config, head_only=bool(h))
                                   for _, _, _, h, *_ in rec.samples.train)
