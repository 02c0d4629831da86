"""Fixtures of the harness tests: a checkout-like root holding a copy of
the benchmark plus one tiny configuration and its two traffic mixes,
added the way a later change adds cells, by files alone."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE.parent))

TINY_GRAPH = {"generator": "power_law_symmetric", "n": 1500, "avg_deg": 3.0,
              "alpha_out": 1.2, "alpha_in": 1.2, "seed": 0}
# the tiny traffic mixes: the cell's own file, scaled down, in bursts that
# the fused driver serves and in single requests that the solo driver does
TINY_TRAFFIC = {
    "k4_hot": {"pool": 8, "burst": 4, "cycles": 200, "check_queries": 8},
    "k4_solo": {"pool": 4, "burst": 1, "cycles": 200, "check_queries": 4},
}


def make_tiny_root(root: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark under ``root`` with the cells ``tiny.<mix>``
    added by new files and new entries of BENCHMARK.json only."""
    bench_dir = root / "benchmarks" / "hcpe"
    shutil.copytree(HERE, bench_dir, ignore=shutil.ignore_patterns(
        ".traces", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs" / "ep_pl.json").read_text())
    cfg.update(name="tiny", graph=TINY_GRAPH)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/hcpe/configs/tiny.json",
                             "reduced": ["n"], "why": "harness tests"})
    traffic = json.loads((HERE / "traffic" / "k4_hot.json").read_text())
    for mix, changes in TINY_TRAFFIC.items():
        (bench_dir / "traffic" / f"tiny_{mix}.json").write_text(
            json.dumps(dict(traffic, **changes)))
        cell = f"tiny.{mix}"
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": f"tiny_{mix}", "chips": 1,
                                   "why": "harness tests"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if "ep.k4_hot" in metric.get("workloads", []):
                metric["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    """The tiny checkout-like root, shared by the session."""
    return make_tiny_root(tmp_path_factory.mktemp("hcpe_root"))


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep runs in the test process off the persistent compile cache."""
    from hcpe import run
    monkeypatch.setattr(run, "use_cache", lambda: "off")
