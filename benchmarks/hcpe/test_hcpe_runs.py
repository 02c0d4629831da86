"""End-to-end tests of the harness on the CPU at a tiny size: sound runs
come out correct and report their cell's metrics, a run without a TPU
reports nothing, and cells and metrics are added by files alone."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from hcpe import run

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
MIXES = ["k4_hot", "k4_solo"]
SEED = 2**31 + 3


@pytest.mark.parametrize("mix", MIXES)
def test_sound_run_is_correct_and_reports_its_metrics(mix, tiny_root,
                                                      no_compile_cache):
    out = run.run_cell(f"tiny.{mix}", SEED, 2.0, False, root=tiny_root,
                       require_tpu=False, log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    assert list(out)[-1] == "checks"
    assert all(v["limit"] == 0 for v in out["checks"].values())
    assert out["device"]["platform"] == "cpu"


def test_traced_cpu_run_reports_no_device_metric(tiny_root, no_compile_cache):
    out = run.run_cell("tiny.k4_hot", SEED, 2.0, True, root=tiny_root,
                       require_tpu=False, log=lambda m: None)
    assert out["correct"]
    assert {"plan.ms_per_query", "enum.ms_per_query.closed",
            "enum.dispatches_per_query"} <= set(out["metrics"])
    assert not {"frontier_roofline", "device.idle.closed"} & \
        set(out["metrics"])
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_a_metric_is_added_by_a_file(tiny_root, tmp_path, no_compile_cache):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    (root / "benchmarks/hcpe/metrics/tiny.completed.py").write_text(
        "def read(rec):\n    return sum(r['ok'] for r in rec['requests'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny.completed", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "front end", "moves": "qps",
        "workloads": ["tiny.k4_hot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = run.run_cell("tiny.k4_hot", SEED, 1.0, True, root=root,
                       require_tpu=False, log=lambda m: None)
    assert out["metrics"]["tiny.completed"]["value"] == out["attempted"]


def bench_command(cwd: pathlib.Path, env_extra: dict
                  ) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in run.HIDING_SWITCHES}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/hcpe/run.py", "--workload", "ep.k4_hot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("case", ["no_tpu", "hiding_switch", "bare_tree"])
def test_command_refuses_and_prints_no_result(case, tmp_path):
    cwd, extra = REPO, {}
    if case == "hiding_switch":
        extra = {"REPRO_DEVICE_ENUM": "off"}
    if case == "bare_tree":
        cwd = tmp_path
        shutil.copy(REPO / "BENCHMARK.json", cwd)
        shutil.copytree(HERE, cwd / "benchmarks" / "hcpe",
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = bench_command(cwd, extra)
    assert proc.returncode != 0
    assert not [line for line in proc.stdout.splitlines()
                if line.startswith("{")]
