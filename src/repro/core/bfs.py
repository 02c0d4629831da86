"""Bounded BFS distances — the first stage of index construction (Alg. 3 L1).

TPU adaptation: the queue BFS of the paper becomes k rounds of pull
relaxation over the graph's device copy (``graph.DeviceGraph``) for a
stack of Q rows at once, on reached-sets bit-packed per vertex.  Each
vertex holds ``ceil(Q / 32)`` 32-bit row masks, bit q set once row q has
reached it, kept as four uint8 planes a mask: a (4 · ceil(Q / 32), n + 1)
array whose columns are vertices.  XLA's TPU gather costs about 2.5 ns a
column of such an array, against 7.7 ns an index of a 1-D uint32 array
(PERF.md §6).  The predecessor lists are grouped by length rounded up to
a power of two (``graph.PredLists``) and the vertices are numbered in
that bucket order, so a round is one gather of the P padded list slots'
columns and an OR down each bucket's ``(width, count)`` block: no (Q, m)
gather and no permutation.  A row's distance at a vertex is the number
of rounds 0..kmax before its bit is set.  One jitted program
(``_stacked_bfs_jit``) runs the rounds from every s of a micro-batch's
cache misses and to every t, puts the distances back in vertex order,
and its one (2, Q, n) int8 result is copied back once
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
from .graph import DeviceGraph, Graph, PredLists


def fits_device(n: int, kmax: int) -> bool:
    """Whether the device BFS encodes hop budgets up to ``kmax`` on ``n``
    vertices: int8 distances (sentinel kmax + 1 <= 127) and int32 places
    (the sentinel place n).  Beyond, the host BFS serves."""
    return kmax <= 126 and n < 2**31 - 1


def _pack(hit: jnp.ndarray) -> jnp.ndarray:
    """(Q, L) bools as (4 · ceil(Q / 32), L) uint8 planes: each column's
    ceil(Q / 32) 32-bit row masks, a byte a plane, row q at bit q % 8 of
    plane q // 8."""
    rows, length = hit.shape
    planes = 4 * -(-rows // 32)
    bits = jnp.pad(hit, ((0, 8 * planes - rows), (0, 0))).reshape(
        planes, 8, length).astype(jnp.uint8)
    shift = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    return jnp.sum(bits << shift, axis=1, dtype=jnp.uint8)


def _unset(planes: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(rows, L) int8: 1 where row q's bit is clear, the inverse of
    ``_pack``."""
    q = jnp.arange(rows, dtype=jnp.uint8)[:, None]
    bit = jnp.repeat(planes, 8, axis=0)[:rows] >> (q % 8)
    return (1 - (bit & 1)).astype(jnp.int8)


def _pull(sent: jnp.ndarray, lists: PredLists) -> jnp.ndarray:
    """Every place's new reach: the OR of ``sent`` over its predecessor
    list, one gather of the padded slots' columns and an OR down each
    bucket's block; 0 at the places without predecessors and at the
    sentinel."""
    planes = sent.shape[0]
    got = sent[:, lists.pred]
    parts, off = [], 0
    for width, count in lists.buckets:
        block = got[:, off:off + width * count].reshape(planes, width, count)
        parts.append(jax.lax.reduce(block, np.uint8(0), jax.lax.bitwise_or,
                                    (1,)))
        off += width * count
    rest = sent.shape[1] - sum(count for _, count in lists.buckets)
    return jnp.concatenate(parts + [jnp.zeros((planes, rest), jnp.uint8)],
                           axis=1)


def _distances(lists: PredLists, roots: jnp.ndarray, excluded: jnp.ndarray,
               kmax: int) -> jnp.ndarray:
    """(Q, n) int8 distances from ``roots`` over ``lists``, sentinel kmax
    + 1, where a row's ``excluded`` vertex relays nothing (no transit
    through it) but may itself be reached; a root of -1 reaches
    nothing."""
    n, rows = lists.pos.shape[0], roots.shape[0]
    place = jnp.arange(n + 1, dtype=jnp.int32)[None, :]

    def at(v):
        # a vertex's place, and for -1 one past the sentinel
        return jnp.where(v >= 0, lists.pos[jnp.maximum(v, 0)], n + 1)[:, None]

    keep = ~_pack(place == at(excluded))

    def relax(_, carry):
        reached, unset = carry
        reached = reached | _pull(reached & keep, lists)
        return reached, unset + _unset(reached, rows)

    reached = _pack(place == at(roots))
    _, unset = jax.lax.fori_loop(0, kmax, relax,
                                 (reached, _unset(reached, rows)))
    return unset[:, lists.pos]


@functools.partial(jax.jit, static_argnames=("kmax",))
def _stacked_bfs_jit(graph: DeviceGraph, srcs: jnp.ndarray,
                     tgts: jnp.ndarray, ks: jnp.ndarray,
                     kmax: int) -> jnp.ndarray:
    """(2, Q, n) int8 bounded distances: ``[0, q]`` from ``srcs[q]`` in
    G - {tgts[q]} and ``[1, q]`` to ``tgts[q]`` in G - {srcs[q]}, after
    ``kmax`` rounds, each row clipped to its own ``ks[q] + 1`` sentinel.
    A row whose endpoints are -1 has no source and stays at the
    sentinel."""
    ds = _distances(graph.fwd, srcs, tgts, kmax)
    dt = _distances(graph.rev, tgts, srcs, kmax)
    return jnp.minimum(jnp.stack([ds, dt]),
                       (ks[None, :, None] + 1).astype(jnp.int8))


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
    are inert).  Counts the launch, its padded and live rows, and the
    padded list slots its rounds gather (kmax rounds in each direction,
    each gathering every slot's column of mask planes once)."""
    kmax = max(k for _, _, k in queries)
    if not fits_device(graph.n, kmax):
        raise ValueError(f"k = {kmax} on n = {graph.n} overflows the device "
                         f"BFS's int8 distances or int32 places")
    dev = graph.device_arrays()
    ss, tt, kk, kmax = _query_rows(queries)
    trace.count("pathenum.index.bfs_launches")
    trace.count("pathenum.index.bfs_rows", ss.shape[0])
    trace.count("pathenum.index.bfs_live_rows", len(queries))
    trace.count("pathenum.index.bfs_gather_slots",
                kmax * (dev.fwd.pred.shape[0] + dev.rev.pred.shape[0]))
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
