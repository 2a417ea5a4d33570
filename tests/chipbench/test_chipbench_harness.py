"""The harness finds every unit by its name, refuses unknown ones and
devices it has no peaks for, takes a new cell, mix and metric from new
files alone, and prints no result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest
from chipbench_kit import ROOT, SEED, SMALL, harness


def test_every_benchmark_entry_resolves_to_its_files():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.Cell.load(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert hasattr(harness.load_driver(cell.mix["driver"]), "Driver")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert harness.load_config(c["name"])["name"] == c["name"]
    for m in bench["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)


@pytest.mark.parametrize(
    "load, name",
    [
        (lambda n: harness.Cell.load(n), "no-such-cell"),
        (harness.load_config, "no-such-config"),
        (harness.load_mix, "no-such-mix"),
        (harness.load_driver, "no_such_driver"),
        (harness.load_metric, "no_such.metric"),
        (harness.peaks, "cpu"),
    ],
)
def test_unknown_names_and_devices_are_refused(load, name):
    with pytest.raises(harness.BenchError):
        load(name)


def test_peaks_of_the_v5e():
    p = harness.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9


def test_a_cell_mix_and_metric_come_from_new_files_alone(tmp_path, monkeypatch):
    # a copy of the benchmark with one more cell, mix, end-to-end and
    # per-layer metric, added as files and entries; no file that was there
    # is edited
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    bench = harness.load_benchmark()
    (tmp_path / "chipbench" / "mixes" / "kron-tc-twice.json").write_text(
        json.dumps({"driver": "closed_loop", "queries": ["triangle", "triangle"]})
    )
    (tmp_path / "chipbench" / "metrics" / "queries.tc.py").write_text(
        "def read(ctx):\n    return ctx.slice.get('queries') or None\n"
    )
    bench["workloads"].append(
        {"name": "kron-tc-twice", "config": "gap-kron", "traffic": "kron-tc-twice", "chips": 1,
         "why": "the warm triangle count, twice a pass"}
    )
    bench["end_to_end"].append(
        {"name": "queries_per_s", "unit": "queries/s", "better": "higher", "bound": 0.03,
         "source": "host_clock", "workloads": ["kron-tc-twice"]}
    )
    bench["end_to_end"][0]["workloads"] = ["kron-standing"]
    bench["per_layer"].append(
        {"name": "queries.tc", "unit": "queries", "better": "higher",
         "source": "program_counter", "layer": "executor", "moves": "queries_per_s",
         "workloads": ["kron-tc-twice"]}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path / "chipbench")
    monkeypatch.setattr(harness, "ROOT", tmp_path)

    cell = harness.Cell.load("kron-tc-twice", harness.load_benchmark(tmp_path))
    assert [m["name"] for m in cell.per_layer] == ["queries.tc"]
    cell.config["params"].update(SMALL["gap-kron"])
    res = harness.execute(cell, SEED, 1.0, False, jax.devices()[:1], t_start=time.perf_counter())
    assert res["correct"], res
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    ctx = harness.MetricContext(reduction=None, slice={"queries": 3}, peaks={})
    assert harness.load_metric("queries.tc").read(ctx) == 3


def _cli(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "kron-standing", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_prints_no_result_without_a_tpu():
    out = _cli(ROOT)
    assert out.returncode == 2, out.stderr
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in harness.load_benchmark()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
