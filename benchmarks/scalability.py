"""Fig. 12 analogue — scalability on a large graph.

The paper's tm graph (1.96B edges) doesn't fit this container's budget;
a 20M-edge power-law graph exercises the same regime: index construction
dominated by the two BFS passes, enumeration throughput ≥1e6 results/s.
BFS here runs through the jitted edge-relaxation (core/bfs.py) — the
vectorized path that maps to the Pallas min-plus kernel on TPU.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

from repro.core import erdos_renyi, build_index
from repro.core import bfs as bfs_mod
from repro.core.enumerate import EngineLimit, enumerate_paths_idx
from repro.core.estimator import walk_count_dp

Row = Tuple[str, float, str]


def run(n: int = 200_000, avg_deg: int = 50, k: int = 5,
        nq: int = 3) -> List[Row]:
    rows: List[Row] = []
    t0 = time.time()
    g = erdos_renyi(n, float(avg_deg), seed=5)
    rows.append(("fig12/graph_build_s", time.time() - t0,
                 f"V={g.n};E={g.m}"))

    rng = np.random.default_rng(0)

    bfs_t = idx_t = opt_t = enum_t = 0.0
    results = 0
    for qi in range(nq):
        s = int(rng.integers(0, n))
        # pick a target within 3 hops so the query has results (§7.1 rule)
        ds, _ = bfs_mod.index_distances(g, s, -1, 3)
        cand = np.nonzero((ds >= 2) & (ds <= 3))[0]
        if cand.size == 0:
            continue
        t = int(cand[rng.integers(0, cand.size)])
        t0 = time.time()
        bfs_mod.index_distances(g, int(s), int(t), k)
        bfs_t += time.time() - t0
        t0 = time.time()
        idx = build_index(g, int(s), int(t), k,
                          dist_fn=bfs_mod.index_distances)
        idx_t += time.time() - t0
        t0 = time.time()
        walk_count_dp(idx)
        opt_t += time.time() - t0
        t0 = time.time()
        try:
            r = enumerate_paths_idx(idx, count_only=True, first_n=2_000_000)
            results += r.count
        except EngineLimit:
            pass
        enum_t += time.time() - t0
    rows.append(("fig12/bfs_s_per_query", bfs_t / nq, ""))
    rows.append(("fig12/index_s_per_query", idx_t / nq, "includes BFS"))
    rows.append(("fig12/optimize_s_per_query", opt_t / nq, ""))
    rows.append(("fig12/throughput_results_per_s",
                 results / max(enum_t, 1e-9), f"results={results}"))
    return rows
