"""Bounded BFS distances — the first stage of index construction (Alg. 3 L1).

TPU adaptation: the queue BFS of the paper becomes k rounds of
edge-parallel relaxation over the graph's device CSR
(``graph.DeviceGraph``), for a stack of Q sources at once.  A round
gathers each edge's predecessor distance for every row, a (Q, m) gather,
and takes the min over each vertex's CSR segment of predecessors.  One
jitted program (``_stacked_bfs_jit``) runs the rounds from every s of a
micro-batch's cache misses and from every t on the reverse graph, and
its one (2, Q, n) int8 result is copied back once
(``stacked_index_distances``).  Rows are padded to a power of two with
inert rows (no source), so a partial burst compiles nothing new.  The
mesh-sharded BFS of distributed/engine.py and the numpy mirror
``core.batch.batched_bounded_bfs`` compute the same distances.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from .graph import DeviceGraph, Graph


def fits_device(n: int, kmax: int) -> bool:
    """Whether the device BFS encodes hop budgets up to ``kmax`` on ``n``
    vertices: int8 distances (sentinel kmax + 1 <= 127) and int32
    segment keys (n·(kmax + 2) < 2^31).  Beyond, the host BFS serves."""
    return kmax <= 126 and n * (kmax + 2) < 2**31


def _relax(dist: jnp.ndarray, pred: jnp.ndarray, owner: jnp.ndarray,
           last: jnp.ndarray, has_pred: jnp.ndarray, excluded: jnp.ndarray,
           inf: jnp.ndarray) -> jnp.ndarray:
    """One round: each vertex takes 1 + the least distance among its
    predecessors ``pred``, where a row's ``excluded`` vertex relaxes
    nothing (no transit through it) but may itself receive a distance.

    The min over each vertex's CSR segment is a running max along the
    sorted edge array: ``owner * (inf + 1) + (inf - d)`` grows with the
    segment, so its running max at the segment's ``last`` entry is the
    segment's own base plus ``inf`` minus its least distance (int32:
    n·(kmax + 2) < 2^31).  A scatter-min, pulled over the sorted
    segments or pushed along the forward edges, is four to five times
    slower on a TPU v5e at web-Google scale (PERF.md §6)."""
    gathered = jnp.where(pred[None, :] == excluded[:, None], inf,
                         dist[:, pred])
    width = inf + 1
    run = jax.lax.cummax(owner[None, :] * width + (inf - gathered), axis=1)
    base = jnp.arange(dist.shape[1], dtype=jnp.int32)[None, :] * width
    seg = jnp.where(has_pred, inf - (run[:, last] - base), inf)
    return jnp.minimum(dist, jnp.minimum(seg, inf - 1) + 1)


@functools.partial(jax.jit, static_argnames=("kmax",))
def _stacked_bfs_jit(graph: DeviceGraph, srcs: jnp.ndarray,
                     tgts: jnp.ndarray, ks: jnp.ndarray,
                     kmax: int) -> jnp.ndarray:
    """(2, Q, n) int8 bounded distances: ``[0, q]`` from ``srcs[q]`` in
    G - {tgts[q]} and ``[1, q]`` to ``tgts[q]`` in G - {srcs[q]}, after
    ``kmax`` rounds, each row clipped to its own ``ks[q] + 1`` sentinel.
    A row whose endpoints are -1 has no source and stays at the
    sentinel."""
    n = graph.indptr.shape[0] - 1
    inf = jnp.int32(kmax + 1)
    col = jnp.arange(n, dtype=jnp.int32)[None, :]

    def bfs(roots, excluded, pred, owner, indptr):
        dist = jnp.where(col == roots[:, None], 0, inf).astype(jnp.int32)
        if pred.shape[0] == 0:
            return dist
        last = jnp.maximum(indptr[1:] - 1, 0)
        has_pred = (indptr[1:] > indptr[:-1])[None, :]
        return jax.lax.fori_loop(
            0, kmax, lambda _, d: _relax(d, pred, owner, last, has_pred,
                                         excluded, inf), dist)

    ds = bfs(srcs, tgts, graph.rsrc, graph.rdst, graph.rindptr)
    dt = bfs(tgts, srcs, graph.dst, graph.src, graph.indptr)
    out = jnp.minimum(jnp.stack([ds, dt]), ks[None, :, None] + 1)
    return out.astype(jnp.int8)


def _query_rows(queries: Sequence[Tuple[int, int, int]]
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """The launch's ``(s, t, k)`` columns as int32 rows padded to a power
    of two, with inert rows (endpoints -1, budget kmax) past the
    queries, and kmax."""
    from ..kernels.ops import _next_pow2   # lazy, as the other drivers
    kmax = max(k for _, _, k in queries)
    rows = _next_pow2(len(queries))
    ss, tt, kk = (np.full(rows, fill, np.int32) for fill in (-1, -1, kmax))
    for i, (s, t, k) in enumerate(queries):
        ss[i], tt[i], kk[i] = s, t, k
    trace.count("pathenum.xfer.h2d_bytes", ss.nbytes + tt.nbytes + kk.nbytes)
    return ss, tt, kk, kmax


def stacked_bfs(graph: Graph, queries: Sequence[Tuple[int, int, int]]
                ) -> jnp.ndarray:
    """The stacked BFS of ``(s, t, k)`` queries on the device: one launch
    over the graph's cached device copy, rows padded to a power of two;
    returns the (2, rows, n) int8 device result (rows past the queries
    are inert).  Counts the launch and its padded and live rows."""
    kmax = max(k for _, _, k in queries)
    if not fits_device(graph.n, kmax):
        raise ValueError(f"k = {kmax} on n = {graph.n} overflows the device "
                         f"BFS's int8 distances or int32 segment keys")
    dev = graph.device_arrays()
    ss, tt, kk, kmax = _query_rows(queries)
    trace.count("pathenum.index.bfs_launches")
    trace.count("pathenum.index.bfs_rows", ss.shape[0])
    trace.count("pathenum.index.bfs_live_rows", len(queries))
    return _stacked_bfs_jit(dev, ss, tt, kk, kmax=kmax)


# the most edges a row's index may keep for the device to list them; an
# index past it takes the host's pass over the edges (build_index)
KEPT_CAP = 4096


@functools.partial(jax.jit, static_argnames=("cap",))
def _kept_edges_jit(graph: DeviceGraph, dist: jnp.ndarray, srcs: jnp.ndarray,
                    tgts: jnp.ndarray, ks: jnp.ndarray, cap: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The edges each row's index keeps, from the stacked BFS's (2, Q, n)
    distances: u -> v with dist_s[u] + 1 + dist_t[v] <= k, v != s and
    u != t, the rules ``build_index`` applies.  Returns (Q, cap) int32
    edge ids, ascending, -1 past the row's count, and the (Q,) counts;
    a count above ``cap`` lists only the first ``cap``."""
    rows = srcs.shape[0]
    if graph.src.shape[0] == 0:
        return (jnp.full((rows, cap), -1, jnp.int32),
                jnp.zeros((rows,), jnp.int32))
    ds, dt = dist[0].astype(jnp.int32), dist[1].astype(jnp.int32)
    keep = ((ds[:, graph.src] + dt[:, graph.dst] < ks[:, None])
            & (graph.dst[None, :] != srcs[:, None])
            & (graph.src[None, :] != tgts[:, None]))
    run = jnp.cumsum(keep, axis=1, dtype=jnp.int32)
    count = run[:, -1]
    # the j-th kept edge is the first position where the running count
    # reaches j
    nth = jnp.arange(1, cap + 1, dtype=jnp.int32)
    pos = jax.vmap(lambda r: jnp.searchsorted(r, nth, side="left"))(run)
    ids = jnp.where(nth[None, :] <= count[:, None], pos, -1)
    return ids.astype(jnp.int32), count


Rows = Tuple[np.ndarray, ...]


def distances_by_block(queries: Sequence[Tuple[int, int, int]], block: int,
                       stack: Callable[[Sequence[Tuple[int, int, int]]],
                                       Rows]) -> List[Rows]:
    """Per-query ``(dist_s, dist_t, ...)`` of ``(s, t, k)`` queries,
    ``block`` queries at a time: ``stack(chunk)`` gives the chunk's
    forward and reverse distance rows, each clipped to its own k + 1
    sentinel (rows past the chunk are ignored), then any per-query
    lists, whose entries follow the int32 distances.  ``block`` bounds
    the (block, m) working set of one stacked BFS."""
    out: List[Rows] = []
    block = max(block, 1)
    for lo in range(0, len(queries), block):
        chunk = queries[lo:lo + block]
        ds, dt, *per_query = stack(chunk)
        out.extend((ds[i].astype(np.int32), dt[i].astype(np.int32),
                    *(p[i] for p in per_query))
                   for i in range(len(chunk)))
    return out


def _copy_back(arrays: Sequence[jnp.ndarray], live: int) -> List[np.ndarray]:
    """One copy of a launch's outputs to the host, the stacked distances
    first; counted in ``pathenum.xfer.d2h_bytes`` and, for the ``live``
    of the launch's padded rows, in ``d2h_live_bytes``."""
    host = jax.device_get(list(arrays))
    nbytes = sum(a.nbytes for a in host)
    trace.count("pathenum.xfer.d2h_bytes", nbytes)
    trace.count("pathenum.xfer.d2h_live_bytes",
                nbytes // host[0].shape[1] * live)
    return host


def stacked_index_distances(graph: Graph,
                            queries: Sequence[Tuple[int, int, int]],
                            block: int = 128) -> List[Rows]:
    """Per-query ``(dist_s, dist_t)`` (int32, sentinel k + 1) of ``(s, t,
    k)`` queries from the device BFS, one launch a block, each launch's
    distances copied back once; byte-identical to
    ``core.batch.batched_index_distances``."""
    def stack(chunk: Sequence[Tuple[int, int, int]]) -> Rows:
        dist, = _copy_back([stacked_bfs(graph, chunk)], len(chunk))
        return dist[0], dist[1]

    return distances_by_block(queries, block, stack)


def stacked_index_inputs(graph: Graph,
                         queries: Sequence[Tuple[int, int, int]],
                         block: int = 128) -> List[Rows]:
    """Per-query ``(dist_s, dist_t, kept)``: the distances of
    ``stacked_index_distances`` and the ascending ids of the edges the
    query's index keeps (``_kept_edges_jit``, on the device beside the
    BFS), or None where they pass ``KEPT_CAP``; one copy back a block.
    ``build_index`` takes ``kept`` in place of its pass over every edge."""
    def stack(chunk: Sequence[Tuple[int, int, int]]) -> Rows:
        dist = stacked_bfs(graph, chunk)
        ss, tt, kk, _ = _query_rows(chunk)
        ids, count = _kept_edges_jit(graph.device_arrays(), dist, ss, tt, kk,
                                     cap=KEPT_CAP)
        dist, ids, count = _copy_back([dist, ids, count], len(chunk))
        kept = [ids[i, :c] if c <= KEPT_CAP else None
                for i, c in enumerate(count[:len(chunk)].tolist())]
        return dist[0], dist[1], kept

    return distances_by_block(queries, block, stack)


def index_distances(graph: Graph, s: int, t: int, k: int):
    """(dist_s, dist_t) per Prop. 4.3: S(s,·|G−{t}) and S(·,t|G−{s}),
    from the device BFS."""
    return stacked_index_distances(graph, [(s, t, k)])[0]


def index_distances_np(graph: Graph, s: int, t: int, k: int):
    """Host reference (queue BFS) — used to cross-check the jitted relaxation."""
    from .oracle import bfs_dist_np
    ds = bfs_dist_np(graph, s, k, reverse=False, excluded=t)
    dt = bfs_dist_np(graph, t, k, reverse=True, excluded=s)
    return ds, dt
