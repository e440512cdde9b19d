"""The benchmark's files: every configuration, traffic mix, client model and
metric loads by the name ``BENCHMARK.json`` or the configuration gives it; a
new mix and a new metric are found as new files with no edit; the count
functions agree with hand counts; the command refuses to run without a
TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import counts, spec

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
MLP = spec.client_model("mlp")


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_loads_its_files():
    bench = _bench()
    assert bench["paths"] == ["benchmarks/chip"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"] and cell.traffic["name"] == w["traffic"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_entries_keep_the_contract():
    bench = _bench()
    names = [c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert e2e == {"uploads_per_s", "reply_p95_ms", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg) and cfg["source"] == c["source"]
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1


def test_every_config_names_a_client_model():
    for c in _bench()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        path = BENCH / "models" / f"{cfg['client_model']}.py"
        assert path.is_file(), path
        model = spec.client_model(cfg["client_model"])
        for name in spec.MODEL_FUNCTIONS:
            assert callable(getattr(model, name)), (cfg["client_model"], name)
        assert (BENCH / cfg["reference"]).is_file()


def test_a_config_without_a_client_model_is_refused(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench_dir = tmp_path / "benchmarks" / "chip"
    path = bench_dir / "configs" / f"{_bench()['workloads'][0]['config']}.json"
    cfg = json.loads(path.read_text())
    del cfg["client_model"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="client_model"):
        spec.load_cell(CELL, bench_dir=bench_dir, root=tmp_path)
    (bench_dir / "models" / "bare.py").write_text("def draw(config, rng, program_seed, seed):\n    pass\n")
    with pytest.raises(AttributeError, match="row_floats"):
        spec.client_model("bare", bench_dir=bench_dir)


def test_config_widths_match_the_program():
    for c in _bench()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        model = spec.client_model(cfg["client_model"])
        model.check_sizes(cfg)  # raises where the widths are not the program's
        assert model.row_floats(cfg) == cfg["row_floats"]


def test_a_new_mix_and_metric_are_found_as_new_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    bench = _bench()
    bench["workloads"].append({"name": f"{CELL}-w1", "config": "har", "traffic": "w1", "chips": 1,
                               "why": "dummy"})
    bench["per_layer"].append({"name": "dummy_share", "unit": "%", "better": "lower",
                               "source": "host_clock", "layer": "event loop (fl/simulator.py)",
                               "moves": "uploads_per_s", "workloads": [f"{CELL}-w1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    bench_dir = tmp_path / "benchmarks" / "chip"
    mix = json.loads((bench_dir / "traffic" / "w5.json").read_text())
    mix.update(name="w1", coalesce_window_s=1.0)
    (bench_dir / "traffic" / "w1.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "dummy_share.py").write_text("def read(run):\n    return 42.0\n")
    cell = spec.load_cell(f"{CELL}-w1", bench_dir=bench_dir, root=tmp_path)
    assert cell.traffic["coalesce_window_s"] == 1.0
    assert "dummy_share" in [m["name"] for m in cell.per_layer]
    assert spec.metric_reader("dummy_share", bench_dir=bench_dir)(None) == 42.0
    other = spec.load_cell(CELL, bench_dir=bench_dir, root=tmp_path)
    assert "dummy_share" not in [m["name"] for m in other.per_layer]


def test_unknown_device_has_no_peaks():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert spec.device_peaks(peaks, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.device_peaks(peaks, "cpu")


HAND = {  # the paper's two client models as the repository builds them
    "har": dict(input_dim=64, hidden=[64], num_classes=6, samples_per_client=96, local_epochs=5),
    "image_recognition": dict(input_dim=128, hidden=[128, 64], num_classes=10,
                              samples_per_client=96, local_epochs=5),
}


@pytest.mark.parametrize("name,row,macs,below", [("har", 4550, 64 * 64 + 64 * 6, 64 * 6),
                                                 ("image_recognition", 25418,
                                                  128 * 128 + 128 * 64 + 64 * 10, 128 * 64 + 64 * 10)])
def test_counts_match_hand_counts(name, row, macs, below):
    cfg = HAND[name]
    assert MLP.row_floats(cfg) == row
    assert MLP.macs_per_sample(cfg) == macs
    assert MLP.train_samples(cfg) == 92  # 96 + 19 generated, 23 held out for test
    assert MLP.train_flops_per_upload(cfg) == 5 * 92 * (2 * macs + 2 * macs + 2 * below)
    head = MLP.layer_macs(cfg)[-1]
    assert MLP.train_flops_per_upload(cfg, head_only=True) == 5 * 92 * (2 * macs + 2 * head)
    ops, nbytes = counts.ingest_chain_cost(100, 4, row)
    assert nbytes == 4 * (100 * row + 2 * 4 * row + 100 * row + 4 * 100)
    assert ops == 100 * (3 * 4 * row + 3 * row + 9 * row)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.least_time_s(ops, nbytes, peaks)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def _run_cmd(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_refuses_a_cpu_backend():
    p = _run_cmd(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    p = _run_cmd(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
