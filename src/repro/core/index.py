"""The light-weight query-dependent index (Section 4.2 / Algorithm 3).

Semantics preserved exactly:
  * ``dist_s[v] = S(s, v | G - {t})`` and ``dist_t[v] = S(v, t | G - {s})``
    (two bounded BFS passes, bfs.py).
  * level sets ``C_i = {v : dist_s[v] <= i  and  dist_t[v] <= k - i}``.
  * ``I_t(v, b)``: out-neighbors v' of v with ``dist_t[v'] <= b`` in O(1) —
    edges are kept only when ``dist_s[u] + 1 + dist_t[v] <= k`` (the paper's
    hash-table H membership rule), sorted by ``(u, dist_t[v])`` and addressed
    through a dense ``(n, k+1)`` end-offset matrix.
  * ``I_s(v, b)``: symmetric reverse index sorted by ``(v, dist_s[u])`` —
    used by the backward DP of Algorithm 5.

TPU adaptation (recorded in DESIGN.md §2): the paper's hash table + counting
sort become one lexsort + scatter-add histogram + cumulative sum; lookups
stay O(1) via the offset matrix.  ``build_index`` is the host (numpy) build;
``build_index_jax`` is the jit-compatible build with identical outputs
(tests/test_index.py asserts bit-equality), enabling on-device index
construction when queries are sharded across a mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bfs
from .graph import Graph


@dataclasses.dataclass
class DeviceIndexArrays:
    """Device (int32) copies of the forward index for the Pallas frontier
    kernel (DESIGN.md §9): ``begin`` (n,), ``end`` (n, k+1) and ``dst``
    (mf,).  ``dst`` is padded to at least one element so the kernel's
    gather always has a valid extent; rows/fan-out padding is the
    kernel wrapper's job (kernels/ops.frontier_expand)."""
    begin: jnp.ndarray
    end: jnp.ndarray
    dst: jnp.ndarray


class _OffsetTable:
    """A dense offset table of ``LightweightIndex`` (``fwd_begin``,
    ``fwd_end``, ``rev_begin``, ``rev_end``; DESIGN.md §2).  A build that
    passes None leaves it unfilled: the first read fills all four from
    the index's sorted edges (``_offsets_from_sorted``) and keeps them.
    The device path reads none of them (``it_count``, ``device_arrays``),
    so a served index never pays for its n·(k+1) host tables."""

    def __set_name__(self, owner, name: str) -> None:
        self.slot = "_" + name

    def __get__(self, obj, owner=None):
        if obj is None:
            raise AttributeError(self.slot)    # no default: a required field
        table = obj.__dict__[self.slot]
        if table is None:
            obj._fill_offsets()
            table = obj.__dict__[self.slot]
        return table

    def __set__(self, obj, value) -> None:
        obj.__dict__[self.slot] = value


@dataclasses.dataclass
class LightweightIndex:
    n: int
    k: int
    s: int
    t: int
    dist_s: np.ndarray        # (n,) int32, sentinel k+1
    dist_t: np.ndarray        # (n,) int32, sentinel k+1
    # forward: edges (u -> v) sorted by (u, dist_t[v]); only index edges kept
    fwd_src: np.ndarray       # (mf,) int32 — u, the row of each edge
    fwd_dst: np.ndarray       # (mf,) int32
    fwd_eid: np.ndarray       # (mf,) int64 — original edge id (constraints ext.)
    fwd_begin: np.ndarray = _OffsetTable()  # (n,) int64
    fwd_end: np.ndarray = _OffsetTable()    # (n, k+1) int64 — end for budget b
    # reverse: edges (u -> v) sorted by (v, dist_s[u])
    rev_src: np.ndarray       # (mf,) int32
    rev_dst: np.ndarray       # (mf,) int32 — v, the row of each edge
    rev_begin: np.ndarray = _OffsetTable()  # (n,) int64
    rev_end: np.ndarray = _OffsetTable()    # (n, k+1) int64 — end for budget b
    level_count: np.ndarray   # (k+1,) int64 — |C_i|
    gamma: np.ndarray         # (k,) float64 — gamma_hat_j (Eq. 5 statistic)

    def _fill_offsets(self) -> None:
        """The four dense offset tables from the sorted edges."""
        fwd = _offsets_from_sorted(self.fwd_src, self.dist_t[self.fwd_dst],
                                   self.n, self.k)
        rev = _offsets_from_sorted(self.rev_dst, self.dist_s[self.rev_src],
                                   self.n, self.k)
        for name, table in zip(("fwd_begin", "fwd_end", "rev_begin",
                                "rev_end"), fwd + rev):
            if self.__dict__["_" + name] is None:
                self.__dict__["_" + name] = table

    # -- O(1) lookups (host convenience; jitted code uses the arrays directly)
    def it(self, v: int, b: int) -> np.ndarray:
        """I_t(v, b): neighbors v' of v with dist_t[v'] <= b."""
        if b < 0:
            return self.fwd_dst[0:0]
        b = min(b, self.k)
        return self.fwd_dst[self.fwd_begin[v]:self.fwd_end[v, b]]

    def is_(self, v: int, b: int) -> np.ndarray:
        """I_s(v, b): in-neighbors v' of v with dist_s[v'] <= b."""
        if b < 0:
            return self.rev_src[0:0]
        b = min(b, self.k)
        return self.rev_src[self.rev_begin[v]:self.rev_end[v, b]]

    def level(self, i: int) -> np.ndarray:
        """I(i) = C_i as a vertex-id array."""
        mask = (self.dist_s <= i) & (self.dist_t <= self.k - i)
        return np.nonzero(mask)[0].astype(np.int32)

    def it_count(self, v, b) -> np.ndarray:
        """|I_t(v, b)| vectorized over v (b scalar), from the sorted
        edges (no dense table)."""
        if b < 0:
            return np.zeros(np.shape(v), dtype=np.int64)
        key = self.__dict__.get("_fwd_key")
        if key is None:
            key = _row_keys(self.fwd_src, self.dist_t[self.fwd_dst], self.k)
            self.__dict__["_fwd_key"] = key
        return _count_within(key, v, min(b, self.k), self.k)

    @property
    def num_index_edges(self) -> int:
        return int(self.fwd_dst.shape[0])

    def device_arrays(self) -> DeviceIndexArrays:
        """The forward index as int32 device arrays for the frontier
        kernel, built once and cached on the index (indexes are immutable
        once built, DESIGN.md §9).  ``begin`` and ``end`` are built on
        the device from the sorted edges (``_device_offsets``), equal to
        the host tables as int32.  ``dst`` pads to the next power of two
        with an inert −1 fill: its length is a traced shape of the jitted
        kernel, so bucketing it keeps recompiles logarithmic in index
        size instead of one per distinct (s, t, k) query."""
        cached = self.__dict__.get("_device_arrays")
        if cached is None:
            mf = int(self.fwd_dst.shape[0])
            mf_pad = 1 << (max(mf, 1) - 1).bit_length()
            # pad edges sit in row n, past every vertex, and add nothing
            src, sec, dst = (np.full(mf_pad, fill, np.int32)
                             for fill in (self.n, self.k + 1, -1))
            src[:mf], dst[:mf] = self.fwd_src, self.fwd_dst
            sec[:mf] = self.dist_t[self.fwd_dst]
            begin, end = _device_offsets(src, sec, n=self.n, k=self.k)
            cached = DeviceIndexArrays(begin=begin, end=end,
                                       dst=jnp.asarray(dst))
            self.__dict__["_device_arrays"] = cached
        return cached

    def memory_bytes(self) -> int:
        tot = 0
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, np.ndarray):
                tot += v.nbytes
        return tot


def _offsets_from_sorted(keys_primary: np.ndarray, keys_secondary: np.ndarray,
                         n: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """begin (n,), end (n, k+1) over arrays already sorted by (primary, sec)."""
    # begin[v] counts the edges with primary < v; end[v, b] also admits
    # primary == v with secondary <= b, so a row without edges is begin[v]
    # in every column and only the rows that hold edges need a lookup —
    # searchsorted over the fused (primary, clipped secondary) key.  An
    # index keeps a sliver of the graph's edges, so this costs O(n) writes
    # rather than n·(k+1) binary searches (DESIGN.md §13)
    width = np.int64(k + 2)
    fused = (keys_primary.astype(np.int64) * width
             + np.minimum(keys_secondary.astype(np.int64), k + 1))
    begin = np.zeros(n, np.int64)
    np.cumsum(np.bincount(keys_primary, minlength=n)[:-1], out=begin[1:])
    end = np.repeat(begin, k + 1).reshape(n, k + 1)
    rows = keys_primary[np.flatnonzero(np.diff(keys_primary, prepend=-1))
                        ].astype(np.int64)
    probes = rows[:, None] * width + np.arange(k + 1, dtype=np.int64)
    end[rows] = np.searchsorted(fused, probes.reshape(-1),
                                side="right").reshape(-1, k + 1)
    return begin, end


def _row_keys(rows: np.ndarray, secondary: np.ndarray, k: int) -> np.ndarray:
    """The int64 key ``row·(k+2) + min(secondary, k+1)`` of edges sorted
    by (row, secondary): ascending, so counts are binary searches."""
    return (rows.astype(np.int64) * (k + 2)
            + np.minimum(secondary.astype(np.int64), k + 1))


def _count_within(key: np.ndarray, v, b: int, k: int) -> np.ndarray:
    """Edges of each row ``v`` whose secondary is at most ``b`` (<= k),
    over ``_row_keys``: ``end[v, b] - begin[v]`` without the tables."""
    base = np.asarray(v, np.int64) * (k + 2)
    return (np.searchsorted(key, base + b, side="right")
            - np.searchsorted(key, base, side="left"))


def _prefix_sum(x):
    """Inclusive prefix sum of a 1-D array, in rows of 1024 and then
    over the rows' totals.  One cumsum along a single row of about 10^6
    entries takes some 40 s to compile for a TPU v5e; this form, under
    one."""
    n = x.shape[0]
    rows = -(-n // 1024)
    inner = jnp.cumsum(jnp.pad(x, (0, rows * 1024 - n)).reshape(rows, 1024),
                       axis=1)
    carry = jnp.cumsum(inner[:, -1]) - inner[:, -1]
    return (inner + carry[:, None]).reshape(-1)[:n]


def _offsets_jnp(primary, secondary, n: int, k: int):
    """begin (n,) and end (n, k+1) int32 over edges sorted by (primary,
    secondary), in jnp: a (primary, secondary) histogram and its prefix
    sums.  Edges with primary n add nothing."""
    cnt2d = jnp.zeros((n + 1, k + 2), dtype=jnp.int32)
    sec = jnp.minimum(secondary, k + 1)
    cnt2d = cnt2d.at[primary, sec].add(1)[:n]
    per_v = cnt2d.sum(axis=1)
    begin = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             _prefix_sum(per_v)[:-1]])
    end = begin[:, None] + jnp.cumsum(cnt2d[:, : k + 1], axis=1)
    return begin, end


_device_offsets = jax.jit(_offsets_jnp, static_argnames=("n", "k"))


def build_index(graph: Graph, s: int, t: int, k: int,
                dist_fn=bfs.index_distances_np,
                edge_mask: Optional[np.ndarray] = None,
                kept: Optional[np.ndarray] = None) -> LightweightIndex:
    """Algorithm 3, host build.

    ``edge_mask`` implements the Appendix-E predicate extension: edges whose
    mask entry is False are filtered before the distance BFS, so constrained
    queries reuse the whole machinery unchanged.  ``dist_fn(g, s, t, k)``
    returns ``(dist_s, dist_t)`` with the sentinel k + 1, as every BFS of
    bfs.py and batch.py does.  ``kept``, where the caller has it, lists
    the ascending ids of the edges that pass the rules below (the device
    computes them beside the distances, ``bfs.stacked_index_inputs``),
    in place of the pass over every edge.  The dense offset tables are
    left for their first read (``_OffsetTable``).
    """
    g = graph
    if edge_mask is not None:
        keep = np.asarray(edge_mask, dtype=bool)
        edges = np.stack([g.esrc[keep], g.edst[keep]], axis=1)
        from .graph import from_edges
        g = from_edges(g.n, edges, dedup=False)
    dist_s, dist_t = dist_fn(g, s, t, k)
    dist_s = np.asarray(dist_s, dtype=np.int32)
    dist_t = np.asarray(dist_t, dtype=np.int32)

    # distance rule (Prop 4.3) + relation-construction rules of §3.1:
    # no edge re-enters s (middle relations live in G-{s}, R_k demands v≠s)
    # and no edge leaves t (only the virtual (t,t) padding, handled by the
    # join enumerator explicitly).  Unless ``kept`` lists the survivors,
    # the distance rule runs over all m edges on the narrowest type that
    # holds two distances (one byte a vertex keeps the gathered tables in
    # cache); the endpoint rules only over its survivors.
    narrow = np.int8 if 2 * (k + 1) <= 127 else np.int32
    near_s, near_t = dist_s.astype(narrow), dist_t.astype(narrow)
    if kept is None:
        keep_ids = np.flatnonzero(near_s[g.esrc] + near_t[g.edst] < k)
        fu, fv = g.esrc[keep_ids], g.edst[keep_ids]
        rule = (fv != s) & (fu != t)
        keep_ids, fu, fv = keep_ids[rule], fu[rule], fv[rule]
    else:
        keep_ids = np.asarray(kept, np.int64)
        fu, fv = g.esrc[keep_ids], g.edst[keep_ids]

    # forward: sort by (u, dist_t[v])
    order_f = np.lexsort((dist_t[fv], fu))
    fu_s, fv_s = fu[order_f], fv[order_f]
    fwd_eid = keep_ids[order_f]

    # reverse: sort by (v, dist_s[u])
    order_r = np.lexsort((dist_s[fu], fv))
    ru_s, rv_s = fu[order_r], fv[order_r]

    # C_i holds v iff dist_s[v] <= i <= k - dist_t[v]: only vertices with
    # dist_s + dist_t <= k lie in any level
    on = np.flatnonzero(near_s + near_t <= k)
    ds_on, dt_on = dist_s[on], dist_t[on]
    ii = np.arange(k + 1)
    lvl = (ds_on[None, :] <= ii[:, None]) & (dt_on[None, :] <= (k - ii)[:, None])
    level_count = lvl.sum(axis=1).astype(np.int64)

    key = _row_keys(fu_s, dist_t[fv_s], k)
    gamma = np.zeros(k, dtype=np.float64)
    for j in range(k):
        cj = on[lvl[j]]
        if cj.size:
            gamma[j] = float(_count_within(key, cj, k - j - 1, k).mean())

    return LightweightIndex(
        n=g.n, k=k, s=s, t=t, dist_s=dist_s, dist_t=dist_t,
        fwd_src=fu_s.astype(np.int32), fwd_dst=fv_s.astype(np.int32),
        fwd_eid=fwd_eid, fwd_begin=None, fwd_end=None,
        rev_src=ru_s.astype(np.int32), rev_dst=rv_s.astype(np.int32),
        rev_begin=None, rev_end=None, level_count=level_count, gamma=gamma)


# ---------------------------------------------------------------------------
# jit-compatible build (identical outputs, static shapes)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "k"))
def _build_index_jax(esrc, edst, dist, n: int, k: int, s, t):
    # dist: the (2, rows, n) stacked BFS of (s, t, k) in row 0
    dist_s = dist[0, 0].astype(jnp.int32)
    dist_t = dist[1, 0].astype(jnp.int32)

    u = esrc.astype(jnp.int32)
    v = edst.astype(jnp.int32)
    keep = ((dist_s[u] + 1 + dist_t[v]) <= k) & (v != s) & (u != t)
    # invalid edges sort to the end: primary key n, secondary k+1
    pf = jnp.where(keep, u, n)
    sf = jnp.where(keep, dist_t[v], k + 1)
    order_f = jnp.lexsort((sf, pf))
    fv_s = jnp.where(keep[order_f], v[order_f], -1)
    fu_s = pf[order_f]
    feid = jnp.where(keep[order_f], order_f, -1)

    offsets = functools.partial(_offsets_jnp, n=n, k=k)

    fwd_begin, fwd_end = offsets(fu_s, jnp.where(fv_s >= 0, dist_t[fv_s], k + 1))

    pr = jnp.where(keep, v, n)
    sr = jnp.where(keep, dist_s[u], k + 1)
    order_r = jnp.lexsort((sr, pr))
    ru_s = jnp.where(keep[order_r], u[order_r], -1)
    rv_s = pr[order_r]
    rev_begin, rev_end = offsets(rv_s, jnp.where(ru_s >= 0, dist_s[ru_s], k + 1))

    ii = jnp.arange(k + 1)
    lvl = (dist_s[None, :] <= ii[:, None]) & (dist_t[None, :] <= (k - ii)[:, None])
    level_count = lvl.sum(axis=1)

    jj = jnp.arange(k)
    budgets = k - jj - 1  # (k,)
    cnt_all = fwd_end[:, :] - fwd_begin[:, None]          # (n, k+1)
    sel = cnt_all[:, budgets].T.astype(jnp.float32)       # (k, n)
    gsum = jnp.where(lvl[:k], sel, 0.0).sum(axis=1)
    gamma = gsum / jnp.maximum(level_count[:k].astype(jnp.float32), 1.0)

    return (dist_s, dist_t, fu_s, fv_s, feid, fwd_begin, fwd_end, ru_s, rv_s,
            rev_begin, rev_end, level_count, gamma)


def build_index_jax(graph: Graph, s: int, t: int, k: int) -> LightweightIndex:
    """Algorithm 3 on the device: the stacked BFS and the build, over the
    graph's cached device copy (``Graph.device_arrays``)."""
    dev = graph.device_arrays()
    dist = bfs.stacked_bfs(graph, [(s, t, k)])
    out = _build_index_jax(dev.src, dev.dst, dist, graph.n, k,
                           jnp.int32(s), jnp.int32(t))
    (dist_s, dist_t, fu_s, fv_s, feid, fwd_begin, fwd_end, ru_s, rv_s,
     rev_begin, rev_end, level_count, gamma) = map(np.asarray, out)
    mf = int((fv_s >= 0).sum())
    return LightweightIndex(
        n=graph.n, k=k, s=s, t=t,
        dist_s=dist_s.astype(np.int32), dist_t=dist_t.astype(np.int32),
        fwd_src=fu_s[:mf].astype(np.int32), fwd_dst=fv_s[:mf].astype(np.int32),
        fwd_eid=feid[:mf].astype(np.int64),
        fwd_begin=fwd_begin.astype(np.int64), fwd_end=fwd_end.astype(np.int64),
        rev_src=ru_s[:mf].astype(np.int32), rev_dst=rv_s[:mf].astype(np.int32),
        rev_begin=rev_begin.astype(np.int64), rev_end=rev_end.astype(np.int64),
        level_count=level_count.astype(np.int64), gamma=gamma.astype(np.float64))
