"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute through the interpreter
(numerics only, not speed), so wall numbers here time the *jnp reference*
path — the structural costs (FLOPs, bytes) per call are derived
analytically and printed alongside.  On TPU the same entry points compile
to Mosaic; the derived column is what the roofline predicts per call.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_index
from repro.core.enumerate import EnumStats, _expand_chunk
from repro.core.graph import PAD
from repro.kernels import ops, ref

Row = Tuple[str, float, str]


def _time(fn, *args, repeat=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeat * 1e6


def _frontier_workload(gname: str, k: int):
    """A representative chunk of one workload graph: build the index for
    a §7.1-style high-degree query, walk the frontier down on the host,
    and hand back the *widest* chunk seen — the shape the device kernel
    spends its time on.  Returns (idx, chunk, depth)."""
    from .workloads import GRAPHS, high_degree_queries
    g = GRAPHS[gname]()
    # widest corridor among a few §7.1 queries: kernel throughput is only
    # meaningful on the chunk shapes the workload actually produces
    idx = max((build_index(g, s, t, k)
               for s, t in high_degree_queries(g, 8, seed=7)),
              key=lambda i: i.num_index_edges)
    def fanout(paths, depth):
        last = paths[:, depth].astype(np.int64)
        return int((idx.fwd_end[last, k - depth - 1]
                    - idx.fwd_begin[last]).sum())

    chunk = np.full((1, k + 1), PAD, np.int32)
    chunk[0, 0] = idx.s
    best = (chunk, 0, fanout(chunk, 0))
    paths, depth = chunk, 0
    while depth + 1 < k:
        exp = _expand_chunk(idx, paths, depth, EnumStats())
        if exp is None:
            break
        parent, pos, vnew, emit, cont = exp
        sel = np.nonzero(cont)[0]
        if not sel.size:
            break
        rows = paths[parent[sel]].copy()
        rows[:, depth + 1] = vnew[sel]
        paths, depth = rows, depth + 1
        if fanout(rows, depth) >= best[2]:
            best = (rows, depth, fanout(rows, depth))
    assert best[2] > 0, (gname, idx.s, idx.t)
    return idx, best[0], best[1]


def frontier_expand() -> List[Row]:
    """Frontier-expansion (device backend) throughput on two workload
    graphs — the enumeration-kernel perf trajectory (DESIGN.md §9).  On
    CPU the kernel runs interpreted, so the wall number tracks the
    interpreter; the derived column carries the structural per-call work
    (edges gathered, candidate slots) that the TPU roofline prices.
    """
    rows: List[Row] = []
    # two regimes on purpose: dense = the wide-frontier case the §9 auto
    # rule routes to the device; pl_hub = the thin-corridor case it keeps
    # on the host (the index prunes hub graphs to a handful of edges)
    for gname, k in (("dense", 4), ("pl_hub", 6)):
        idx, chunk, depth = _frontier_workload(gname, k)
        dev = idx.device_arrays()
        last = chunk[:, depth].astype(np.int64)
        cnt = idx.fwd_end[last, k - depth - 1] - idx.fwd_begin[last]
        max_deg = int(cnt.max())

        def call(_chunk=chunk, _dev=dev, _t=idx.t, _md=max_deg, _d=depth):
            return ops.frontier_expand(_chunk, _dev.begin, _dev.end,
                                       _dev.dst, depth=_d, t=_t, max_deg=_md)

        us = _time(lambda: call()[4])
        edges = int(cnt.sum())
        slots = chunk.shape[0] * max_deg
        rows.append((f"kernels/frontier_expand_{gname}_r{chunk.shape[0]}", us,
                     f"edges={edges};slots={slots};"
                     f"edges_per_s={edges / max(us, 1e-9) * 1e6:.0f}"))
    return rows


def run() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)
    rows.extend(frontier_expand())

    n = 1024
    adj = np.where(rng.random((n, n)) < 0.01, 1.0, 1e9).astype(np.float32)
    dist = np.full(n, 1e9, np.float32)
    dist[0] = 0
    f = jax.jit(lambda a, d: ref.minplus_spmv_ref(a, d, 1e9))
    us = _time(f, jnp.array(adj), jnp.array(dist))
    rows.append(("kernels/minplus_spmv_n1024", us,
                 f"bytes={(n*n+2*n)*4};tpu_mem_term_us="
                 f"{(n*n+2*n)*4/819e9*1e6:.2f}"))

    q = 128
    adjm = (rng.random((n, n)) < 0.01).astype(np.float32)
    cnts = rng.random((n, q)).astype(np.float32)
    f2 = jax.jit(ref.counting_spmm_ref)
    us = _time(f2, jnp.array(adjm), jnp.array(cnts))
    flops = 2 * n * n * q
    rows.append(("kernels/counting_spmm_n1024_q128", us,
                 f"flops={flops};tpu_compute_term_us={flops/197e12*1e6:.3f}"))

    return rows
