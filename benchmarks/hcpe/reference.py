"""Plain reference for hop-constrained s-t path enumeration (HcPE).

P(s, t, k) is the set of simple paths from s to t with at most k edges
whose interior avoids s and t.  This module computes it with numpy
alone, independently of the program under test: a bounded reverse BFS
gives every vertex's distance to t, and the paths grow from s one level
at a time, a partial path of d edges ending at v kept only while
d + dist(v, t) <= k and v is not already on it.  Rows come back sorted,
in the program's layout: (r, k + 1) int32, padded with -1 after t.

``simple=False`` drops the "not already on it" test and so returns
walks: the control of the benchmark's correctness check, which breaks
the one guarantee (simple paths) that the deployment states.
"""
from __future__ import annotations

import numpy as np

from .gen import Csr, csr_ptr, gather

PAD = -1


def hops(csr: Csr, root: int, k: int, reverse: bool = False) -> np.ndarray:
    """Hops from ``root`` to every vertex (to ``root`` from every vertex
    if ``reverse``), capped at ``k + 1``."""
    dist = np.full(csr.n, k + 1, np.int32)
    dist[root] = 0
    front = np.array([root], np.int64)
    for d in range(1, k + 1):
        _, nb = csr.gather(front, reverse=reverse)
        nb = np.unique(nb[dist[nb] > d])
        if nb.size == 0:
            break
        dist[nb] = d
        front = nb
    return dist


def paths(csr: Csr, s: int, t: int, k: int, simple: bool = True
          ) -> np.ndarray:
    """P(s, t, k) as sorted (r, k + 1) int32 rows (walks if not
    ``simple``).  The edges that level d may take are cut down first to
    those u -> v with hops(s, u) <= d - 1 and hops(v, t) <= k - d, so
    no level gathers neighbours that cannot lead to t in time."""
    if s == t:
        raise ValueError("s and t must be distinct")
    d_s = hops(csr, s, k)
    d_t = hops(csr, t, k, reverse=True)
    src = csr.sources()
    out = []
    cur = np.array([[s]], np.int32)
    for d in range(1, k + 1):
        if cur.shape[0] == 0:
            break
        use = ((d_s[src] <= d - 1) & (d_t[csr.indices] <= k - d)
               & (csr.indices != s))
        row, nb = gather(csr_ptr(csr.n, src[use]), csr.indices[use],
                         cur[:, -1])
        if simple:
            keep = ~(cur[row] == nb[:, None]).any(axis=1)
            row, nb = row[keep], nb[keep]
        grown = np.concatenate([cur[row], nb[:, None]], axis=1)
        done = grown[:, -1] == t
        full = np.full((int(done.sum()), k + 1), PAD, np.int32)
        full[:, :d + 1] = grown[done]
        out.append(full)
        cur = grown[~done]
    if not out:
        return np.zeros((0, k + 1), np.int32)
    return sort_rows(np.concatenate(out))


def sort_rows(rows: np.ndarray) -> np.ndarray:
    """Rows in lexicographic order (int comparison, column 0 first)."""
    rows = np.asarray(rows, np.int32)
    if rows.shape[0] == 0:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


def row_keys(rows: np.ndarray) -> np.ndarray:
    """One opaque, comparable key per row, for set tests."""
    rows = np.ascontiguousarray(rows, np.int32)
    return rows.view(np.dtype((np.void, rows.dtype.itemsize
                               * rows.shape[1]))).ravel()
