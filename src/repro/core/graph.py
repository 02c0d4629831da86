"""Graph container + generators for the PathEnum engine.

The engine's canonical representation is a static CSR pair (forward and
reverse) plus flat edge lists.  Vertices are int32 ids in [0, n).  All arrays
are host numpy; ``DeviceGraph`` holds the edge list and each vertex's
predecessor lists, grouped by pow2 length, as int32 device arrays for the
stacked BFS (core/bfs.py), uploaded once per graph object and cached on
it (``Graph.device_arrays``).  Distances are bounded by the hop
constraint ``k`` so the sentinel ``INF_DIST`` is any value > k; we use
0x3FFF_FFFF to stay addition-safe in int32.

Graphs are immutable values, but deployments stream (DESIGN.md §12): a
fraud graph ingests live transactions between queries.  Mutation is
therefore *versioned copying* — ``with_edges`` (and the ``add_edges`` /
``remove_edges`` conveniences) rebuild the CSR around the new edge set
and return a new ``Graph`` whose monotone ``version`` is bumped by one.
Every index-cache key derived from a graph folds the version in
(core/batch.py), so an index built against version v can never answer a
query against version v+1 — the streaming invalidation contract.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace

INF_DIST = np.int32(0x3FFFFFFF)
PAD = np.int32(-1)

# one upload per graph object, even when server threads ask together
_UPLOAD_LOCK = threading.Lock()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PredLists:
    """Every vertex's predecessor list in one BFS direction, grouped by
    length rounded up to a power of two (DESIGN.md §4).  ``pos`` (n,) is
    each vertex's place in bucket order: the buckets by width, ascending,
    then the vertices without predecessors, each group in id order; place
    n is a sentinel that relays nothing.  ``pred`` (P,) holds bucket after
    bucket, bucket ``(width, count)`` as a ``(width, count)`` block in
    row-major order: entry ``[j, i]`` is the place of the j-th
    predecessor of the bucket's i-th vertex, or n past its list.
    ``buckets`` is static, read from the graph's own degrees."""
    pred: jnp.ndarray
    pos: jnp.ndarray
    buckets: Tuple[Tuple[int, int], ...] = dataclasses.field(
        metadata=dict(static=True))


def pred_lists(indptr: np.ndarray, indices: np.ndarray) -> PredLists:
    """``PredLists`` of the CSR ``(indptr, indices)``, whose segment v
    lists v's predecessors, as host arrays: one O(n + m) pass."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr).astype(np.int64)
    # log2 of each list's pow2 width: the bit length of deg - 1
    lg = np.frexp(np.maximum(deg - 1, 0).astype(np.float64))[1]
    key = np.where(deg > 0, lg, 64)
    order = np.argsort(key, kind="stable")
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    lgs, counts = np.unique(lg[deg > 0], return_counts=True)
    widths = np.left_shift(1, lgs).astype(np.int64)
    starts = np.cumsum(counts) - counts
    offs = np.cumsum(widths * counts) - widths * counts
    owner = np.repeat(np.arange(n), deg)
    b = np.searchsorted(lgs, lg[owner])
    rank = np.arange(owner.shape[0]) - indptr[owner]
    pred = np.full(int((widths * counts).sum()), n, np.int32)
    pred[offs[b] + rank * counts[b] + pos[owner] - starts[b]] = \
        pos[indices]
    return PredLists(pred, pos.astype(np.int32),
                     tuple((int(w), int(c)) for w, c in zip(widths, counts)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """A ``Graph`` on the device (DESIGN.md §4): ``src`` and ``dst``
    (m,), the edges in forward-CSR order, and the predecessor lists of
    the BFS's two directions: ``fwd`` the in-neighbours, for distances
    from a source, ``rev`` the out-neighbours, for distances to a
    target."""
    src: jnp.ndarray
    dst: jnp.ndarray
    fwd: PredLists
    rev: PredLists


@dataclasses.dataclass(frozen=True)
class Graph:
    """Directed graph in CSR (forward + reverse) with flat edge lists.

    ``version`` is the streaming-mutation epoch (DESIGN.md §12): 0 for a
    freshly built graph, and bumped by one on every ``with_edges`` /
    ``add_edges`` / ``remove_edges`` copy.  It is monotone per mutation
    *lineage* — the engine folds it into every index-cache key, so
    pre-mutation indexes are unreachable the instant a mutated copy
    starts serving.
    """

    n: int
    # forward CSR
    indptr: np.ndarray    # (n+1,) int64
    indices: np.ndarray   # (m,)   int32, dst sorted within each src slice
    # reverse CSR
    rindptr: np.ndarray   # (n+1,) int64
    rindices: np.ndarray  # (m,)   int32
    # flat edge list (same order as forward CSR)
    esrc: np.ndarray      # (m,) int32
    edst: np.ndarray      # (m,) int32
    # streaming-mutation epoch (DESIGN.md §12); part of the cache key
    version: int = 0

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    def out_degree(self, v: int) -> int:
        return int(self.indptr[v + 1] - self.indptr[v])

    def neighbors(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]:self.indptr[v + 1]]

    def in_neighbors(self, v: int) -> np.ndarray:
        return self.rindices[self.rindptr[v]:self.rindptr[v + 1]]

    def reverse(self) -> "Graph":
        return Graph(self.n, self.rindptr, self.rindices, self.indptr,
                     self.indices, self.rindices_src(), self.redst())

    def rindices_src(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int32),
                         np.diff(self.rindptr).astype(np.int64))

    def redst(self) -> np.ndarray:
        return self.rindices

    def device_arrays(self) -> DeviceGraph:
        """The graph's device copy, uploaded on first use and cached on
        this object.  A graph is immutable and every mutation makes a new
        object (``with_edges``), so the copy never goes stale and each
        graph version is uploaded once: the ``pathenum.index.graph_upload``
        span, its bytes counted in ``pathenum.xfer.h2d_bytes``."""
        cached = self.__dict__.get("_device_arrays")
        if cached is not None:
            return cached
        with _UPLOAD_LOCK:
            cached = self.__dict__.get("_device_arrays")
            if cached is None:
                with trace.span("pathenum.index.graph_upload"):
                    host = DeviceGraph(
                        np.asarray(self.esrc, np.int32),
                        np.asarray(self.edst, np.int32),
                        pred_lists(self.rindptr, self.rindices),
                        pred_lists(self.indptr, self.indices))
                    cached = jax.block_until_ready(jax.device_put(host))
                trace.count("pathenum.xfer.h2d_bytes", sum(
                    a.nbytes for a in jax.tree_util.tree_leaves(host)))
                self.__dict__["_device_arrays"] = cached
        return cached

    # -- streaming mutation (DESIGN.md §12) ---------------------------------

    def edge_list(self) -> np.ndarray:
        """The edge set as an (m, 2) int64 array in forward-CSR order."""
        return np.stack([self.esrc.astype(np.int64),
                         self.edst.astype(np.int64)], axis=1)

    def with_edges(self, add: Optional[np.ndarray] = None,
                   remove: Optional[np.ndarray] = None) -> "Graph":
        """Versioned copy with ``add`` edges inserted and ``remove``
        edges deleted (DESIGN.md §12).

        Both arguments are (r, 2) arrays of directed ``(src, dst)``
        pairs; endpoints must lie in [0, n).  Removals run first, then
        insertions, so passing the same edge in both re-inserts it.
        Removing an edge the graph does not hold raises ValueError (a
        streaming feed out of sync with its graph is a bug worth
        catching, not masking); inserting an edge that already exists is
        a no-op (the edge relation is a set, like ``from_edges``), and
        self-loops are dropped as everywhere else.  The copy's
        ``version`` is ``self.version + 1`` even when the edge set ends
        up unchanged — callers observing the version see every mutation.
        """
        edges = self.edge_list()
        if remove is not None:
            rem = np.asarray(remove, dtype=np.int64).reshape(-1, 2)
            self._check_range(rem, "remove")
            if rem.size:
                cur_keys = edges[:, 0] * self.n + edges[:, 1]
                rem_keys = rem[:, 0] * self.n + rem[:, 1]
                present = np.isin(rem_keys, cur_keys)
                if not present.all():
                    missing = rem[~present][0]
                    raise ValueError(
                        f"cannot remove edge ({int(missing[0])}, "
                        f"{int(missing[1])}): not in the graph")
                edges = edges[~np.isin(cur_keys, rem_keys)]
        if add is not None:
            ins = np.asarray(add, dtype=np.int64).reshape(-1, 2)
            self._check_range(ins, "add")
            edges = np.concatenate([edges, ins], axis=0)
        rebuilt = from_edges(self.n, edges)
        return dataclasses.replace(rebuilt, version=self.version + 1)

    def add_edges(self, edges: np.ndarray) -> "Graph":
        """``with_edges(add=edges)`` — the streaming-insert convenience."""
        return self.with_edges(add=edges)

    def remove_edges(self, edges: np.ndarray) -> "Graph":
        """``with_edges(remove=edges)`` — the streaming-delete
        convenience; every edge must currently exist."""
        return self.with_edges(remove=edges)

    def _check_range(self, pairs: np.ndarray, what: str) -> None:
        if pairs.size and not ((pairs >= 0).all() and (pairs < self.n).all()):
            raise ValueError(f"{what} edges must have endpoints in "
                             f"[0, {self.n})")


def from_edges(n: int, edges: np.ndarray, dedup: bool = True) -> Graph:
    """Build a Graph from an (m, 2) int array of directed edges.

    Self-loops are dropped (a simple path never uses one); duplicate edges are
    deduplicated by default (the edge relation of the join model is a set).
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if edges.size:
        keep = edges[:, 0] != edges[:, 1]
        edges = edges[keep]
    if dedup and edges.size:
        edges = np.unique(edges, axis=0)
    src = edges[:, 0] if edges.size else np.zeros(0, np.int64)
    dst = edges[:, 1] if edges.size else np.zeros(0, np.int64)

    def csr(a, b):
        order = np.lexsort((b, a))
        a_s, b_s = a[order], b[order]
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(indptr, a_s + 1, 1)
        np.cumsum(indptr, out=indptr)
        return indptr, b_s.astype(np.int32), a_s.astype(np.int32)

    indptr, indices, esrc = csr(src, dst)
    rindptr, rindices, _ = csr(dst, src)
    return Graph(n=n, indptr=indptr, indices=indices, rindptr=rindptr,
                 rindices=rindices, esrc=esrc, edst=indices)


# ---------------------------------------------------------------------------
# Generators (benchmark + test workloads; real datasets are not bundled)
# ---------------------------------------------------------------------------

def erdos_renyi(n: int, avg_deg: float, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    return from_edges(n, np.stack([src, dst], axis=1))


def power_law(n: int, avg_deg: float, alpha: float = 1.2, seed: int = 0) -> Graph:
    """Directed preferential-attachment-ish graph (heavy-tailed out/in degree).

    Mirrors the paper's social/web workloads where high-degree hubs create
    large search spaces (the `s,t in V'` query sets of Section 7.1).
    """
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg)
    # Zipfian endpoint sampling
    ranks = np.arange(1, n + 1, dtype=np.float64)
    probs = ranks ** (-alpha)
    probs /= probs.sum()
    perm_out = rng.permutation(n)
    perm_in = rng.permutation(n)
    src = perm_out[rng.choice(n, size=m, p=probs)]
    dst = perm_in[rng.choice(n, size=m, p=probs)]
    return from_edges(n, np.stack([src, dst], axis=1))


def layered_dag(layers: int, width: int, fanout: float, seed: int = 0) -> Graph:
    """Layered DAG with dense inter-layer wiring: many s-t paths, no cycles.

    This is the walk==path regime of Example 5.2 (G0): every walk the engine
    generates is a valid path, so invalid-partial counts are ~0.
    """
    rng = np.random.default_rng(seed)
    n = layers * width + 2
    s, t = n - 2, n - 1
    edges = []
    first = np.arange(width)
    for v in first:
        edges.append((s, v))
    for l in range(layers - 1):
        base_a, base_b = l * width, (l + 1) * width
        cnt = int(width * fanout)
        a = rng.integers(0, width, size=cnt) + base_a
        b = rng.integers(0, width, size=cnt) + base_b
        edges.extend(zip(a.tolist(), b.tolist()))
    for v in range((layers - 1) * width, layers * width):
        edges.append((v, t))
    return from_edges(n, np.array(edges, dtype=np.int64))


def grid(rows: int, cols: int, bidirectional: bool = True) -> Graph:
    n = rows * cols
    edges = []
    for r in range(rows):
        for c in range(cols):
            v = r * cols + c
            if c + 1 < cols:
                edges.append((v, v + 1))
                if bidirectional:
                    edges.append((v + 1, v))
            if r + 1 < rows:
                edges.append((v, v + cols))
                if bidirectional:
                    edges.append((v + cols, v))
    return from_edges(n, np.array(edges, dtype=np.int64))


def complete(n: int) -> Graph:
    src, dst = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    return from_edges(n, np.stack([src.ravel(), dst.ravel()], axis=1))


def random_graph_suite(seed: int = 0) -> dict:
    """Small named workload suite used by tests and benchmarks."""
    return {
        "er_small": erdos_renyi(64, 3.0, seed),
        "er_dense": erdos_renyi(48, 6.0, seed + 1),
        "pl_hub": power_law(96, 4.0, seed=seed + 2),
        "dag": layered_dag(4, 8, 3.0, seed + 3),
        "grid": grid(6, 6),
    }
