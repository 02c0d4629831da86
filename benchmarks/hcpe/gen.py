"""Graph and query generation for the HcPE benchmark.

These are the benchmark's own copies, independent of the program:

* ``power_law_edges``: the power-law generator of ``core/graph.py``
  (Zipfian endpoint draws over two independent vertex permutations),
  drawing the same edges for the same seed, with the out- and the
  in-endpoint exponents apart so that both can be fitted to a source;
* ``symmetric_edges``: every edge also added reversed, self-loops
  dropped, duplicates removed, as the chip smoke test builds its graph;
* ``Csr``: forward and reverse adjacency of an edge list;
* ``walk_counts``: the benchmark's own count of a query's walks, which
  bounds its work;
* ``sample_pairs``: the paper's §7.1 online queries (s and t drawn
  uniformly from V', the top 10% of vertices by degree, with
  dist(s, t) <= 3);
* ``deal_bursts`` / ``burst_order``: the pool dealt into bursts of equal
  work, and the order in which a run asks for them.

The graph and the pool stand for a deployment's data and are drawn from
the seeds that the configuration and the traffic mix fix; the run's
``--seed`` draws the order of the requests.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

# fixed stream ids, so that one seed drives independent generators
GRAPH, POOL, ORDER, SAMPLE = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of one run."""
    return np.random.default_rng([int(stream), int(seed)])


def power_law_edges(n: int, avg_deg: float, alpha_out: float,
                    alpha_in: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """(m, 2) int64 directed edges with Zipfian out-endpoints
    (rank ** -alpha_out) and in-endpoints (rank ** -alpha_in) over two
    independent permutations of the vertices; m = int(n * avg_deg)
    draws, duplicates and self-loops included."""
    m = int(n * avg_deg)
    ranks = np.arange(1, n + 1, dtype=np.float64)

    def probs(alpha: float) -> np.ndarray:
        p = ranks ** (-alpha)
        return p / p.sum()

    perm_out = rng.permutation(n)
    perm_in = rng.permutation(n)
    src = perm_out[rng.choice(n, size=m, p=probs(alpha_out))]
    dst = perm_in[rng.choice(n, size=m, p=probs(alpha_in))]
    return np.stack([src, dst], axis=1).astype(np.int64)


def symmetric_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """Each edge in both directions, without self-loops or duplicates,
    sorted by (src, dst)."""
    e = np.asarray(edges, np.int64)
    e = e[e[:, 0] != e[:, 1]]
    keys = np.unique(np.concatenate([e[:, 0] * n + e[:, 1],
                                     e[:, 1] * n + e[:, 0]]))
    return np.stack([keys // n, keys % n], axis=1)


def build_edges(graph_cfg: dict) -> np.ndarray:
    """The configuration's edge list, drawn from its own ``seed``."""
    kind = graph_cfg["generator"]
    if kind != "power_law_symmetric":
        raise ValueError(f"unknown graph generator {kind!r}")
    n = int(graph_cfg["n"])
    e = power_law_edges(n, float(graph_cfg["avg_deg"]),
                        float(graph_cfg["alpha_out"]),
                        float(graph_cfg["alpha_in"]),
                        rng_for(int(graph_cfg["seed"]), GRAPH))
    return symmetric_edges(n, e)


@dataclasses.dataclass
class Csr:
    """Forward and reverse CSR of a directed edge list."""
    n: int
    indptr: np.ndarray
    indices: np.ndarray
    rindptr: np.ndarray
    rindices: np.ndarray

    @classmethod
    def from_edges(cls, n: int, edges: np.ndarray) -> "Csr":
        """Both adjacencies of an (m, 2) edge list; neighbours sorted."""
        e = np.asarray(edges, np.int64).reshape(-1, 2)

        def one(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray,
                                                       np.ndarray]:
            order = np.lexsort((b, a))
            return csr_ptr(n, a), b[order].astype(np.int32)

        fp, fi = one(e[:, 0], e[:, 1])
        rp, ri = one(e[:, 1], e[:, 0])
        return cls(n, fp, fi, rp, ri)

    def gather(self, verts: np.ndarray, reverse: bool = False
               ) -> Tuple[np.ndarray, np.ndarray]:
        """(row, neighbour) for every out- (or in-) neighbour of each
        vertex in ``verts``; ``row`` indexes ``verts``."""
        if reverse:
            return gather(self.rindptr, self.rindices, verts)
        return gather(self.indptr, self.indices, verts)

    def sources(self) -> np.ndarray:
        """The source vertex of each forward CSR entry."""
        return np.repeat(np.arange(self.n, dtype=np.int32),
                         np.diff(self.indptr))


def gather(ptr: np.ndarray, idx: np.ndarray, verts: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(row, neighbour) pairs of the CSR ``(ptr, idx)`` for ``verts``."""
    verts = np.asarray(verts, np.int64)
    lo = ptr[verts]
    deg = ptr[verts + 1] - lo
    row = np.repeat(np.arange(verts.shape[0]), deg)
    start = np.repeat(lo - np.cumsum(deg) + deg, deg)
    return row, idx[start + np.arange(row.shape[0])]


def csr_ptr(n: int, src: np.ndarray) -> np.ndarray:
    """The CSR offsets of a source-sorted edge list."""
    ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=ptr[1:])
    return ptr


def hubs(csr: Csr) -> np.ndarray:
    """V' of §7.1: vertices whose out-degree is in the top 10%."""
    deg = np.diff(csr.indptr)
    return np.nonzero(deg >= max(np.quantile(deg, 0.9), 1))[0]


def walk_counts(csr: Csr, s: int, t: int, k: int) -> np.ndarray:
    """Number of s-t walks of exactly 1..k edges (entry L - 1 for length
    L) whose interior avoids s and t.  Their sum bounds a query's simple
    paths, and the first nonzero entry is at dist(s, t).

    Met in the middle: ``fwd[i]`` counts the walks of i edges from s to
    each vertex, ``bwd[j]`` those of j edges from each vertex to t, and
    a walk of i + j + 1 edges is one of each joined by an edge."""
    def forward(depth: int) -> List[np.ndarray]:
        out = []
        verts, w = np.array([s], np.int64), np.ones(1)
        for _ in range(depth):
            row, nb = csr.gather(verts)
            acc = np.bincount(nb, weights=w[row], minlength=csr.n)
            acc[s] = acc[t] = 0.0
            out.append(acc)
            verts = np.flatnonzero(acc)
            w = acc[verts]
        return out

    def backward(depth: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        out = [(np.array([t], np.int64), np.ones(1))]
        for _ in range(depth):
            verts, w = out[-1]
            row, nb = csr.gather(verts, reverse=True)
            keep = (nb != s) & (nb != t)
            nb, inv = np.unique(nb[keep], return_inverse=True)
            out.append((nb, np.bincount(inv, weights=w[row[keep]],
                                        minlength=nb.shape[0])))
        return out

    counts = np.zeros(k)
    counts[0] = float(np.any(csr.indices[csr.indptr[s]:csr.indptr[s + 1]]
                             == t))
    fwd = forward((k + 1) // 2)
    bwd = backward(k // 2 - 1)
    for length in range(2, k + 1):
        i = (length + 1) // 2
        verts, w = bwd[length - i - 1]
        row, u = csr.gather(verts, reverse=True)
        counts[length - 1] = float(w[row] @ fwd[i - 1][u])
    return counts


def sample_pairs(csr: Csr, count: int, k: int, max_dist: int,
                 rng: np.random.Generator) -> List[Tuple[int, int, int]]:
    """``count`` §7.1 queries (s, t, k): s and t drawn uniformly from V'
    with dist(s, t) <= ``max_dist``.  Endpoints are pairwise distinct, so
    that no two queries share a walk or an index."""
    vp = hubs(csr)
    pool: List[Tuple[int, int, int]] = []
    used: set = set()
    for _ in range(200 * count):
        if len(pool) == count:
            return pool
        s, t = (int(v) for v in rng.choice(vp, 2, replace=False))
        if s in used or t in used:
            continue
        if walk_counts(csr, s, t, k)[:max_dist].sum() > 0:
            pool.append((s, t, k))
            used.update((s, t))
    if len(pool) == count:
        return pool
    raise RuntimeError(f"only {len(pool)} of {count} query pairs satisfy "
                       f"the constraints")


def deal_bursts(csr: Csr, pool: List[Tuple[int, int, int]], size: int
                ) -> List[List[Tuple[int, int, int]]]:
    """The pool in bursts of ``size`` queries of about equal work: sorted
    by walk count and dealt out in snake order (0, 1, .., b-1, b-1, .., 0,
    0, 1, ..)."""
    if len(pool) % size:
        raise ValueError(f"a pool of {len(pool)} is no whole number of "
                         f"bursts of {size}")
    count = len(pool) // size
    order = sorted(pool, key=lambda q: (walk_counts(csr, *q).sum(), q))
    bursts: List[List[Tuple[int, int, int]]] = [[] for _ in range(count)]
    for i, q in enumerate(order):
        lap, pos = divmod(i, count)
        bursts[pos if lap % 2 == 0 else count - 1 - pos].append(q)
    return bursts


def burst_order(count: int, cycles: int, rng: np.random.Generator
                ) -> np.ndarray:
    """Which burst each round of requests asks for: ``cycles`` random
    permutations of range(count), one after the other, so that each
    cycle of ``count`` rounds asks for every burst once."""
    return np.concatenate([rng.permutation(count) for _ in range(cycles)])
