"""Tests of the benchmark's yardstick: generation, the plain reference,
the metric readers and the trace reduction (CPU, tiny sizes)."""
from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from hcpe import devtrace, gen, reference, run

HERE = pathlib.Path(__file__).resolve().parent


def small_csr(seed: int, n: int = 1500, avg_deg: float = 3.0) -> gen.Csr:
    cfg = {"generator": "power_law_symmetric", "n": n, "avg_deg": avg_deg,
           "alpha_out": 1.2, "alpha_in": 1.2, "seed": seed}
    return gen.Csr.from_edges(n, gen.build_edges(cfg))


def test_generator_matches_the_program_generator():
    from repro.core import from_edges, power_law
    g0 = power_law(400, 4.0, seed=3)
    g1 = from_edges(400, gen.power_law_edges(400, 4.0, 1.2, 1.2,
                                             np.random.default_rng(3)))
    assert np.array_equal(g0.indptr, g1.indptr)
    assert np.array_equal(g0.indices, g1.indices)


def test_configuration_matches_what_it_states():
    cfg = json.loads((HERE / "configs" / "ep_pl.json").read_text())
    n = cfg["graph"]["n"]
    edges = gen.build_edges(cfg["graph"])
    deg = np.bincount(edges[:, 0], minlength=n)
    at_seed = cfg["graph_at_seed"]
    assert edges.shape[0] == at_seed["edges"]
    assert int(deg.max()) == at_seed["max_degree"]
    assert abs(edges.shape[0] / cfg["published"]["edges"] - 1) < 0.01
    assert abs(at_seed["max_in_hub_degree"]
               / cfg["published"]["max_in_degree"] - 1) < 0.05
    assert abs(at_seed["max_out_hub_degree"]
               / cfg["published"]["max_out_degree"] - 1) < 0.05


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_data_is_fixed_and_the_order_follows_the_run_seed(seed):
    csr = small_csr(0)
    assert np.array_equal(csr.indices, small_csr(0).indices)
    assert not np.array_equal(csr.indices, small_csr(1).indices)
    traffic = json.loads((HERE / "traffic" / "k4_hot.json").read_text())
    traffic.update(pool=16, burst=4, cycles=3)
    a, b, c = (run.Traffic(csr, traffic, x) for x in (seed, seed, seed + 1))
    assert a.pool == b.pool == c.pool and a.bursts == c.bursts
    assert a.window == b.window and a.window != c.window
    assert a.warmup == a.bursts + a.bursts


def test_pool_and_bursts_keep_their_rules():
    csr = small_csr(5)
    traffic = json.loads((HERE / "traffic" / "k4_hot.json").read_text())
    traffic.update(pool=16, burst=4, cycles=3)
    tr = run.Traffic(csr, traffic, 5)
    ends = [v for q in tr.pool for v in q[:2]]
    assert len(ends) == len(set(ends))
    vprime = set(gen.hubs(csr).tolist())
    for s, t, k in tr.pool:
        assert s in vprime and t in vprime and k == 4
        assert gen.walk_counts(csr, s, t, k)[:3].sum() > 0
    assert sorted(q for b in tr.bursts for q in b) == sorted(tr.pool)
    assert all(len(b) == 4 for b in tr.bursts)
    # every cycle of the window asks for each burst once
    for lap in range(3):
        cycle = tr.window[4 * lap:4 * lap + 4]
        assert sorted(cycle) == sorted(tr.bursts)
    work = [sum(gen.walk_counts(csr, *q).sum() for q in b)
            for b in tr.bursts]
    sizes = sorted(gen.walk_counts(csr, *q).sum() for q in tr.pool)
    # snake dealing: no burst is off the mean by more than the largest
    # query
    assert max(work) - min(work) <= sizes[-1]


def test_reference_and_walk_counts_agree_with_the_oracle():
    from repro.core import oracle
    from repro.core.graph import random_graph_suite
    for name, g in random_graph_suite(0).items():
        csr = gen.Csr.from_edges(g.n, np.stack([g.esrc, g.edst], axis=1))
        rng = np.random.default_rng(1)
        for _ in range(4):
            s, t = (int(v) for v in rng.choice(g.n, 2, replace=False))
            dist = oracle.bfs_dist_np(g, s, 6, excluded=t)[t]
            for k in (2, 3, 4, 5):
                got = reference.paths(csr, s, t, k)
                assert [tuple(int(x) for x in r if x >= 0) for r in got] \
                    == oracle.enumerate_paths(g, s, t, k), (name, s, t, k)
                walks = gen.walk_counts(csr, s, t, k)
                assert walks.sum() == oracle.count_walks(g, s, t, k)
                assert reference.paths(csr, s, t, k, simple=False
                                       ).shape[0] == walks.sum()
                first = np.flatnonzero(walks)
                assert (first[0] + 1 if first.size else None) == \
                    (int(dist) if dist <= k else None)


def canned_record(trace: bool = True) -> dict:
    """A record as ``run_cell`` builds it, with round numbers."""
    items = [{"plan": "dfs", "k": 4, "edges_accessed": 1000,
              "partials_generated": 100, "results": 50, "fused": True,
              "shared": False},
             {"plan": "join", "k": 4, "edges_accessed": 10**9,
              "partials_generated": 10**9, "results": 7, "fused": False,
              "shared": False}]
    batch = {"queries": 8, "distinct": 4, "distance_s": 0.5, "index_s": 0.3,
             "optimize_s": 0.02, "enumerate_s": 2.0, "total_s": 3.0,
             "hits": 2, "misses": 2, "fused_queries": 3, "items": items}
    requests = [{"sent": 0.1 * i, "ok": True} for i in range(10)]
    return {
        "k": 4, "setup_s": 42.0, "window_s": 5.0, "requests": requests,
        "batches": [batch, dict(batch)],
        "cache": {"hits": 3, "misses": 1},
        "compiles": {"count": 2, "seconds": 1.5, "cache_hits": 2},
        "dispatches": 16, "fanouts": {}, "drivers": {},
        "peak": {"hbm_bytes_per_s": 1e9},
        "trace": {"busy_s": 1.0, "window_s": 4.0,
                  "module_s": {"jit__frontier_fused_jit": 2e-6,
                               "jit_concatenate": 1.0},
                  "device_ops": [], "idle_gaps": []} if trace else None,
    }


EXPECTED = {
    "qps": 2.0, "setup_s": 42.0, "plan.ms_per_query": 5.0,
    "enum.ms_per_query.closed": 500.0, "enum.dispatches_per_query": 2.0,
    # 2 batches x (4 * 1000 + 4 * 5 * 100) bytes at 1 GB/s over 2 us
    "frontier_roofline": 100.0 * 12000 / 1e9 / 2e-6,
    "device.idle.closed": 75.0, "jit.compiles.closed": 2,
    "index.hit_rate": 75.0,
}


def test_every_metric_has_a_reader(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert names == set(EXPECTED)
    assert names == {p.name[:-3] for p in (HERE / "metrics").glob("*.py")}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reader_on_a_canned_record(name):
    read = run._reader(HERE / "metrics" / f"{name}.py")
    assert read(canned_record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["frontier_roofline", "device.idle.closed"])
def test_device_readers_read_nothing_without_a_trace(name):
    read = run._reader(HERE / "metrics" / f"{name}.py")
    assert read(canned_record(trace=False)) is None


def test_qps_reads_nothing_when_no_request_was_answered():
    rec = canned_record()
    for r in rec["requests"]:
        r["ok"] = False
    assert run._reader(HERE / "metrics" / "qps.py")(rec) is None


def test_trace_reduction_on_canned_events():
    ms = 1_000_000
    ev = {"window_ns": 100 * ms,
          "devices": [{"ops": [("fusion.1", "jit_f", 10 * ms, 20 * ms),
                               ("fusion.2", "", 15 * ms, 30 * ms),
                               ("copy", "", 60 * ms, 70 * ms)],
                       "modules": [("jit_f", "", 10 * ms, 30 * ms),
                                   ("jit_g", "", 60 * ms, 70 * ms)]}],
          "host": [("hcpe.batch", 0, 55 * ms), ("hcpe.submit", 0, 100 * ms),
                   ("np.work", 35 * ms, 50 * ms)]}
    out = devtrace.reduce(ev)
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["module_s"] == pytest.approx({"jit_f": 0.020, "jit_g": 0.010})
    assert dict(out["device_ops"]) == pytest.approx(
        {"jit_f/fusion.1": 0.010, "jit_f/fusion.2": 0.015,
         "jit_g/copy": 0.010})
    # gaps: 0-10 (hcpe.batch), 30-60 (np.work at 45), 70-100 (none)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"hcpe.batch": 0.010, "np.work": 0.030, "no host span": 0.030})


def test_trace_reduction_on_a_recorded_tpu_trace():
    # two small jitted programs run three times each inside an
    # ``hcpe.batch`` span, traced on one TPU v5e chip (testdata/)
    out = devtrace.reduce_dir(HERE / "testdata")
    assert out["window_s"] == pytest.approx(0.321308494)
    assert out["busy_s"] == pytest.approx(5.33e-05)
    assert out["module_s"] == pytest.approx({"jit__lambda": 5.3366e-05})
    assert out["device_ops"][0][0] == "jit__lambda/%fusion.2"
    assert sum(v for _, v in out["device_ops"]) <= out["busy_s"] * 1.001
    gaps = dict(out["idle_gaps"])
    assert set(gaps) == {"hcpe.batch", "no host span"}
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(
        out["window_s"])
