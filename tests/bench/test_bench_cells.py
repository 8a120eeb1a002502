"""The on-chip benchmark finds a cell's pieces by name
(benchmarks/chip/chipbench/cells.py), so a cell, configuration, traffic
mix or metric is added with files and entries alone; and ``run.py``
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import cells  # noqa: E402


def test_every_committed_cell_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["workloads"]
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cells.job_module(cell).run
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_a_cell_added_as_files_and_entries_is_found(tmp_path):
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH / "traffic", bench_dir / "traffic")
    shutil.copytree(BENCH / "metrics", bench_dir / "metrics")
    shutil.copytree(BENCH / "configs", bench_dir / "configs")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a new configuration, traffic mix, metric and cell: files + entries
    cfg = json.loads((BENCH / "configs" / "yi6b-s8.json").read_text())
    cfg["arch"]["n_layers"] = 2
    (bench_dir / "configs" / "yi6b-s16.json").write_text(json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / "lm-retrain.b4s512.t10.json")
                    .read_text())
    tr["density"] = 0.5
    (bench_dir / "traffic" / "lm-retrain.b4s512.t50.json").write_text(
        json.dumps(tr))
    (bench_dir / "metrics" / "steps_seen.train.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    bench["configs"].append({"name": "yi6b-s16", "source": "x",
                             "file": "benchmarks/chip/configs/yi6b-s16.json",
                             "reduced": ["n_layers"], "why": "x"})
    bench["workloads"].append({"name": "yi6b-s16.retrain.t50",
                               "config": "yi6b-s16",
                               "traffic": "lm-retrain.b4s512.t50",
                               "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("yi6b-s16.retrain.t50")
    bench["per_layer"].append({"name": "steps_seen.train", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "train_tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("yi6b-s16.retrain.t50",
                           bench_file=tmp_path / "BENCHMARK.json",
                           bench_dir=bench_dir)
    assert cell.config["arch"]["n_layers"] == 2
    assert cell.traffic["density"] == 0.5
    assert cells.job_module(cell).__name__ == "chipbench.jobs.lm_retrain"
    names = {m["name"] for m in cell.per_layer}
    assert "steps_seen.train" in names            # no workloads key: moves
    assert "bsmm_roofline.train" not in names     # listed for other cells
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    read = cells.metric_reader("steps_seen.train", bench_dir=bench_dir)
    assert read(type("Ctx", (), {"steps": 7})()) == 7.0


def test_an_unknown_cell_or_job_is_an_error(tmp_path):
    with pytest.raises(cells.CellError):
        cells.load_cell("no-such-cell")
    cell = cells.load_cell("yi6b-s8.retrain.t10")
    cell.traffic = dict(cell.traffic, job="no_such_job")
    with pytest.raises(cells.CellError):
        cells.job_module(cell)
    with pytest.raises(cells.CellError):
        cells.metric_reader("no-such-metric")


def run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "yi6b-s8.retrain.t10", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_exits_nonzero_without_a_tpu_and_prints_no_result():
    p = run_py(ROOT, {})
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "correct" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "correct" not in p.stdout
