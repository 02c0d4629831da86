"""BatchPathEnum — the online-workload engine (DESIGN.md §4).

The paper's headline metrics are measured on *batches* of queries (the
1000-query online sets of §7.1), yet the Figure-2 pipeline is strictly
per-query.  Batch HcPE processing (Yuan et al., arXiv:2312.01424) shows the
serving wins come from cross-query sharing; this module brings three of
those sharing levers to the PathEnum pipeline:

  1. **result dedup** — identical ``(s, t, k)`` queries in a batch run the
     pipeline once; duplicates receive the same ``EnumResult`` object.
  2. **index cache** — ``LightweightIndex`` builds are cached in an LRU
     keyed on ``(graph_id, s, t, k, edge_mask_hash, graph_version)`` that
     persists across batches, so recurring queries (the hot s-t pairs of a
     production workload) skip the build entirely.  Cache stats (hits /
     misses / evictions) are first-class — globally and per tenant — so
     callers can assert on reuse; per-tenant capacity quotas bound a noisy
     tenant's cache footprint (DESIGN.md §8).  ``graph_version`` is the
     streaming-mutation epoch (DESIGN.md §12): a mutated graph's queries
     key to fresh entries, so a pre-mutation index can never serve them.
  3. **stacked BFS** — the two bounded-BFS distance passes of every
     cache-missing query are stacked into one (Q, n) frontier matrix and
     relaxed together.  Where the engine's backend resolves to the
     device, one jitted program runs them over the graph's device copy,
     and a second lists the edges each index keeps
     (bfs.stacked_index_inputs); on the host one
     ``minimum.reduceat`` over the CSR per hop serves all Q queries
     (``batched_bounded_bfs``, the numpy mirror of the device program
     and of the mesh-vmapped BFS in distributed/engine.py).  Both give
     the same distances, so the indexes are byte-identical.

The planner still runs once per *distinct* query — plans are per-query
decisions (§6) and do not share — and enumeration reuses the per-query
machinery unchanged, so every count is byte-identical to sequential
``PathEnum.count`` (tests/test_batch.py asserts this).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import trace
from . import bfs
from . import planner as planner_mod
from . import sharing as sharing_mod
from .enumerate import (EnumResult, EnumStats, enumerate_paths_idx,
                        resolve_backend)
from .graph import Graph, from_edges
from .index import LightweightIndex, build_index
from .join import enumerate_paths_join
from .pathenum import PathEnum
from .planner import DEFAULT_TAU, Plan

# The engine's cache key.  ``graph_id`` is the tenant dimension
# (DESIGN.md §8): one engine — and therefore one LRU — serves many tenant
# graphs, and the id keeps their entries (and stats, and eviction
# pressure) apart.  Single-graph callers never see it: every entry point
# defaults to ``DEFAULT_GRAPH_ID``.  ``graph_version`` is the tenant
# graph's streaming-mutation epoch (DESIGN.md §12): mutating a graph bumps
# it, so every post-mutation lookup misses the pre-mutation entries by
# construction — correctness never depends on an eager purge.
# (graph_id, s, t, k, edge_mask_hash, graph_version)
QueryKey = Tuple[str, int, int, int, int, int]

DEFAULT_GRAPH_ID = "default"


def tenant_of(key: Union[QueryKey, Tuple[int, ...]]) -> str:
    """The tenant a cache key belongs to.

    ``QueryKey``s carry their ``graph_id`` first (6-tuples since the
    streaming ``graph_version`` dimension, 5-tuples before it — both
    fold the same way); legacy all-int ``(s, t, k, edge_mask_hash)``
    keys (pre-tenancy callers poking the cache directly) fold onto
    ``DEFAULT_GRAPH_ID`` (DESIGN.md §8's single-graph compatibility
    contract).
    """
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return DEFAULT_GRAPH_ID


def edge_mask_hash(edge_mask: Optional[np.ndarray]) -> int:
    """Stable 64-bit hash of an edge mask (0 for the unmasked graph)."""
    if edge_mask is None:
        return 0
    packed = np.packbits(np.asarray(edge_mask, dtype=bool))
    return int.from_bytes(hashlib.blake2b(packed.tobytes(),
                                          digest_size=8).digest(), "big")


@dataclasses.dataclass
class CacheStats:
    """Monotone hit/miss/eviction counters for one cache scope — the whole
    ``IndexCache`` or one tenant's slice of it (DESIGN.md §4, §8)."""
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def lookups(self) -> int:
        """Total lookups: hits + misses (evictions are not lookups)."""
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        """Hits over lookups; 0.0 (not NaN) when nothing was looked up."""
        return self.hits / self.lookups if self.lookups else 0.0

    def snapshot(self) -> "CacheStats":
        """A value copy, for later ``delta`` arithmetic."""
        return CacheStats(self.hits, self.misses, self.evictions)

    def delta(self, since: "CacheStats") -> "CacheStats":
        """Counters accumulated since ``since`` (an earlier snapshot)."""
        return CacheStats(self.hits - since.hits, self.misses - since.misses,
                          self.evictions - since.evictions)


class IndexCache:
    """Tenant-aware LRU over ``LightweightIndex`` keyed on ``QueryKey``
    (``(graph_id, s, t, k, edge_mask_hash, graph_version)``; legacy
    all-int 4-tuple keys fold onto ``DEFAULT_GRAPH_ID`` via
    ``tenant_of``).  DESIGN.md §4, §8 and — for the ``graph_version``
    dimension — §12.

    A hit moves the entry to the MRU slot; inserting past ``capacity``
    evicts the global LRU entry.  On top of the global bound, each tenant
    may carry a *quota* (``set_quota``): inserting past it evicts that
    tenant's own LRU entry first, so a noisy tenant churns its own slice
    of the cache and never squeezes out its neighbors' entries.  Stats are
    kept both globally (``stats``) and per tenant (``stats_for``).
    Indexes are immutable once built, so sharing one object across
    queries, batches and tenants is safe.
    """

    def __init__(self, capacity: int = 256,
                 tenant_quotas: Optional[Dict[str, int]] = None) -> None:
        if capacity < 0:
            raise ValueError("capacity must be >= 0")
        self.capacity = capacity
        self.stats = CacheStats()
        self._entries: "collections.OrderedDict[QueryKey, LightweightIndex]" \
            = collections.OrderedDict()
        self._quotas: Dict[str, int] = {}
        self._tenant_stats: Dict[str, CacheStats] = {}
        # per-tenant LRU-ordered key index (mirrors _entries' recency per
        # tenant) so quota eviction pops a tenant's LRU in O(1) instead
        # of scanning the global OrderedDict
        self._tenant_keys: "Dict[str, collections.OrderedDict]" = {}
        for gid, quota in (tenant_quotas or {}).items():
            self.set_quota(gid, quota)

    def __len__(self) -> int:
        return len(self._entries)

    def tenant_len(self, graph_id: str) -> int:
        """Entries currently held for one tenant."""
        return len(self._tenant_keys.get(graph_id, ()))

    def stats_for(self, graph_id: str) -> CacheStats:
        """This tenant's live hit/miss/eviction counters (zero if never
        seen); the same mutable object is returned across calls, so
        ``snapshot``/``delta`` arithmetic works per tenant too."""
        return self._tenant_stats.setdefault(graph_id, CacheStats())

    def tenant_ids(self) -> Tuple[str, ...]:
        """Every tenant the cache knows about — ids holding live entries
        plus ids with historical stats (a retired tenant's counters
        survive ``drop_tenant`` for post-mortems, DESIGN.md §8).  This is
        the iteration surface of the metrics control plane
        (serving/metrics.py, DESIGN.md §12)."""
        ids = dict.fromkeys(self._tenant_keys)
        ids.update(dict.fromkeys(self._tenant_stats))
        return tuple(ids)

    def quota_for(self, graph_id: str) -> Optional[int]:
        """The tenant's entry quota, or None when only the global
        ``capacity`` bounds it."""
        return self._quotas.get(graph_id)

    def set_quota(self, graph_id: str, quota: Optional[int]) -> None:
        """Bound (or unbound, with None) one tenant's entry count; if the
        tenant already exceeds the new quota its LRU entries are evicted
        immediately."""
        if quota is None:
            self._quotas.pop(graph_id, None)
            return
        if quota < 0:
            raise ValueError("tenant quota must be >= 0")
        self._quotas[graph_id] = quota
        while self.tenant_len(graph_id) > quota:
            self._evict_tenant_lru(graph_id)

    def get(self, key: QueryKey) -> Optional[LightweightIndex]:
        """Look one key up; a hit refreshes its LRU position.  Updates the
        global and the key's tenant counters."""
        tenant = tenant_of(key)
        tstats = self.stats_for(tenant)
        idx = self._entries.get(key)
        if idx is None:
            self.stats.misses += 1
            tstats.misses += 1
            return None
        self._entries.move_to_end(key)
        self._tenant_keys[tenant].move_to_end(key)
        self.stats.hits += 1
        tstats.hits += 1
        return idx

    def put(self, key: QueryKey, idx: LightweightIndex) -> None:
        """Insert (or refresh) one entry, evicting first the owning
        tenant's LRU past its quota, then the global LRU past
        ``capacity``.  A zero quota (or zero capacity) stores nothing."""
        tenant = tenant_of(key)
        quota = self._quotas.get(tenant)
        if self.capacity == 0 or quota == 0:
            return
        if key in self._entries:
            self._entries.move_to_end(key)
            self._tenant_keys[tenant].move_to_end(key)
            self._entries[key] = idx
            return
        if quota is not None:
            while self.tenant_len(tenant) >= quota:
                self._evict_tenant_lru(tenant)
        while len(self._entries) >= self.capacity:
            self._evict(next(iter(self._entries)))
        self._entries[key] = idx
        self._tenant_keys.setdefault(
            tenant, collections.OrderedDict())[key] = None

    def _evict(self, key: QueryKey) -> None:
        tenant = tenant_of(key)
        del self._entries[key]
        tkeys = self._tenant_keys[tenant]
        del tkeys[key]
        if not tkeys:
            del self._tenant_keys[tenant]
        self.stats.evictions += 1
        self.stats_for(tenant).evictions += 1

    def _evict_tenant_lru(self, graph_id: str) -> None:
        self._evict(next(iter(self._tenant_keys[graph_id])))

    def drop_tenant(self, graph_id: str) -> int:
        """Administratively drop every entry (and the quota) of one tenant
        — the cache half of ``GraphRegistry.retire``.  Returns the number
        of entries dropped; unlike quota/capacity pressure this is not
        counted as evictions (it is a retirement, not churn), but the
        tenant's historical stats survive for post-mortems."""
        doomed = self._tenant_keys.pop(graph_id, None) or ()
        for k in doomed:
            del self._entries[k]
        self._quotas.pop(graph_id, None)
        return len(doomed)

    def clear(self) -> None:
        """Drop all entries and reset stats (global and per-tenant) — a
        fresh-cache baseline, so post-clear hit/miss/eviction counters
        describe only the new epoch.  Tenant quotas survive: they are
        configuration, not state."""
        self._entries.clear()
        self._tenant_keys.clear()
        self._tenant_stats.clear()
        self.stats = CacheStats()


# ---------------------------------------------------------------------------
# Stacked-frontier BFS: all cache-missing queries relax together
# ---------------------------------------------------------------------------

def batched_bounded_bfs(indptr: np.ndarray, indices: np.ndarray, n: int,
                        srcs: np.ndarray, excluded: np.ndarray,
                        kmax: int) -> np.ndarray:
    """(Q, n) bounded distances via stacked edge-parallel relaxation.

    ``indices`` must hold, per CSR segment of ``indptr``, the *predecessor*
    ids of each vertex (the reverse CSR for forward distances, the forward
    CSR for reverse distances).  Semantics match oracle.bfs_dist_np: the
    per-row ``excluded`` vertex contributes no relaxations (no transit) but
    may still receive a distance.  Rows relax simultaneously — one
    ``minimum.reduceat`` per hop covers every query — which is the whole
    point: the per-hop cost is one O(Q·m) segmented min instead of Q queue
    traversals.  Returns distances with sentinel ``kmax + 1``.
    """
    Q = int(len(srcs))
    INF = np.int32(kmax + 1)
    dist = np.full((Q, n), INF, dtype=np.int32)
    if Q == 0:
        return dist
    dist[np.arange(Q), np.asarray(srcs, np.int64)] = 0
    m = int(indices.shape[0])
    if m == 0:
        return dist
    starts = indptr[:-1].astype(np.int64)
    has_pred = (np.diff(indptr) > 0)[None, :]        # (1, n)
    pred = indices.astype(np.int64)                   # (m,) grouped by vertex
    exc = np.asarray(excluded, np.int64)[:, None]     # (Q, 1)
    # pred-free vertices have starts == m, out of reduceat's index range;
    # an INF pad column makes index m valid WITHOUT clamping (clamping to
    # m-1 would truncate the preceding vertex's segment and drop its last
    # predecessor edge from the min)
    pad_col = np.full((Q, 1), INF, dtype=np.int32)
    for _ in range(kmax):
        gathered = dist[:, pred]                      # (Q, m) gather
        np.putmask(gathered, pred[None, :] == exc, INF)
        contrib = np.concatenate([gathered, pad_col], axis=1)  # (Q, m+1)
        seg = np.minimum.reduceat(contrib, starts, axis=1)     # (Q, n)
        seg = np.where(has_pred, seg, INF)
        new = np.minimum(dist, np.minimum(seg, INF - 1) + 1)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


def batched_index_distances(graph: Graph, queries: Sequence[Tuple[int, int, int]],
                            block: int = 128) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-query ``(dist_s, dist_t)`` for a list of ``(s, t, k)`` queries.

    Stacks every query's forward pass into one relaxation (and likewise the
    reverse passes), runs to the batch's max k, then clips each row to its
    own hop budget — values ≤ k equal the bounded queue BFS exactly, values
    beyond collapse onto the same ``k + 1`` sentinel, so the downstream
    index build is byte-identical to the sequential path.  ``block`` bounds
    the (block, m) gather working set.
    """
    def stack(chunk: Sequence[Tuple[int, int, int]]
              ) -> Tuple[np.ndarray, np.ndarray]:
        ss, tt, kk = (np.array(col, np.int64) for col in zip(*chunk))
        kmax = int(kk.max())
        # forward: predecessors of v are the reverse-CSR neighbors
        ds = batched_bounded_bfs(graph.rindptr, graph.rindices, graph.n,
                                 ss, tt, kmax)
        # reverse: predecessors (in the reverse graph) are forward neighbors
        dt = batched_bounded_bfs(graph.indptr, graph.indices, graph.n,
                                 tt, ss, kmax)
        return (np.minimum(ds, kk[:, None] + 1),
                np.minimum(dt, kk[:, None] + 1))

    return bfs.distances_by_block(queries, block, stack)


# ---------------------------------------------------------------------------
# Batch results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchItem:
    """Per-query outcome inside a batch (duplicates share ``result``)."""
    s: int
    t: int
    k: int
    result: EnumResult
    plan: Plan
    index_cached: bool          # index came from the LRU (no build)
    deduplicated: bool          # enumeration reused an earlier item's result
    latency_seconds: float      # attributable work for THIS query
    shared: bool = False        # enumerated via a shared group walk (§13)
    fused: bool = False         # enumerated via a fused device launch (§9)


@dataclasses.dataclass
class BatchTiming:
    """Per-phase attributable seconds for one batch (DESIGN.md §4);
    component times are CPU work and merge as sums, the wall-clock span
    merges as interval union (serving/hcpe._merge_outputs)."""
    distance_seconds: float = 0.0
    index_seconds: float = 0.0
    optimize_seconds: float = 0.0
    enumerate_seconds: float = 0.0
    total_seconds: float = 0.0
    # wall-clock span of the batch in time.perf_counter() coordinates;
    # lets concurrent batches merge as max-of-overlapping rather than a
    # sum (serving/hcpe._merge_outputs).  0.0 = span unknown.
    started_at: float = 0.0
    ended_at: float = 0.0


@dataclasses.dataclass
class BatchOutput:
    """One ``BatchPathEnum.run``'s results: per-query items (input order),
    phase timing, the cache-stats delta observed during the run, and the
    tenant (``graph_id``) the batch ran against (DESIGN.md §4, §8)."""
    items: List[BatchItem]
    timing: BatchTiming
    cache_stats: CacheStats          # delta for this batch
    distinct_queries: int
    graph_id: str = DEFAULT_GRAPH_ID  # the tenant this batch served
    sharing_groups: int = 0          # shared walks executed (DESIGN.md §13)
    shared_queries: int = 0          # distinct queries served off a walk
    fused_queries: int = 0           # distinct queries in the fused launch
    fused_dispatches: int = 0        # kernel dispatches the fusion issued

    @property
    def counts(self) -> np.ndarray:
        """Per-query result counts, input order."""
        return np.array([it.result.count for it in self.items], np.int64)

    @property
    def enum_stats(self) -> EnumStats:
        """Merged Fig.-6 enumeration counters (edges accessed, partials,
        invalid partials, results, chunks) across the batch's *distinct*
        results — deduplicated items share their twin's ``EnumResult``
        object and are counted once, so the merge reflects work done,
        not work served."""
        agg = EnumStats()
        seen = set()
        for it in self.items:
            if id(it.result) in seen:
                continue
            seen.add(id(it.result))
            agg.merge(it.result.stats)
        return agg

    @property
    def total_results(self) -> int:
        """Sum of all per-query counts."""
        return int(self.counts.sum())

    def latency_percentiles(self, qs: Sequence[int] = (50, 90, 99)
                            ) -> Dict[str, float]:
        """Attributable per-query latency percentiles in milliseconds."""
        lats = np.array([it.latency_seconds for it in self.items])
        if lats.size == 0:
            return {f"p{q}_ms": 0.0 for q in qs}
        return {f"p{q}_ms": float(np.percentile(lats, q) * 1e3) for q in qs}

    @property
    def throughput_qps(self) -> float:
        """Queries served per wall-clock second of this batch."""
        return len(self.items) / max(self.timing.total_seconds, 1e-12)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class BatchPathEnum:
    """Batched front-end over the Figure-2 pipeline (DESIGN.md §4, §8).

    Accepts ``(s, t, k)`` triples against one graph per call; shares work
    across the batch (dedup, index LRU, stacked BFS) and across calls (the
    LRU persists on the engine).  The engine itself is graph-agnostic:
    each ``run`` names its tenant via ``graph_id`` and the cache keeps the
    tenants' entries apart, so one engine (one LRU, one set of knobs)
    serves a whole ``GraphRegistry``.  ``engine`` parameters mirror
    PathEnum, including the ``backend`` knob steering IDX-DFS expansion
    onto the host or the Pallas device kernel (DESIGN.md §9).
    """

    def __init__(self, tau: float = DEFAULT_TAU, chunk_size: int = 16384,
                 max_partials: Optional[int] = 20_000_000,
                 cache_capacity: int = 256, bfs_block: int = 128,
                 tenant_quotas: Optional[Dict[str, int]] = None,
                 backend: str = "host", sharing: str = "auto",
                 fused: str = "auto") -> None:
        if sharing not in ("auto", "off"):
            raise ValueError(f"unknown sharing mode {sharing!r}")
        if fused not in ("auto", "off"):
            raise ValueError(f"unknown fused mode {fused!r}")
        self.engine = PathEnum(tau=tau, chunk_size=chunk_size,
                               max_partials=max_partials, backend=backend)
        self.cache = IndexCache(capacity=cache_capacity,
                                tenant_quotas=tenant_quotas)
        self.bfs_block = bfs_block
        # cross-query sharing knob (DESIGN.md §13): "auto" groups and
        # shares where profitable, "off" pins the exact solo pipeline;
        # either way results are byte-identical (tests/test_sharing.py).
        self.sharing = sharing
        # fused-launch knob (DESIGN.md §9): "auto" packs the batch's
        # device-eligible dfs-plan queries into fused multi-query kernel
        # launches (one dispatch per expansion round for the whole
        # micro-batch), "off" pins the solo per-query dispatch stream;
        # results are byte-identical either way
        # (tests/test_fused_launch.py).
        self.fused = fused
        self.group_cache = sharing_mod.GroupIndexCache(capacity=64)

    # -- index acquisition --------------------------------------------------
    def _indexes_for(self, graph: Graph, keys: List[QueryKey],
                     edge_mask: Optional[np.ndarray],
                     precomputed: Optional[Dict[QueryKey, Tuple[np.ndarray,
                                                                np.ndarray]]],
                     timing: BatchTiming,
                     group_builds: bool = False
                     ) -> Dict[QueryKey, Tuple[LightweightIndex, bool]]:
        """Resolve each distinct key to (index, was_cached).

        Cache misses on the unmasked graph batch their BFS passes through
        the stacked relaxation, on the device where the engine's backend
        resolves there (``resolve_backend`` before any index exists) and
        the hop budgets fit its encoding (``bfs.fits_device``); masked
        queries fall back to the host and the per-query build (the
        mask changes the graph under the BFS).  A miss whose kept edges
        the device listed builds alone from them, with no pass over the
        graph's edges.

        With ``group_builds`` (sharing enabled, DESIGN.md §13) two more
        construction levers engage, both byte-identical to the solo
        build: masked batches filter the graph *once* (so every masked
        miss builds — and batch-BFSes — on one shared filtered graph
        instead of re-filtering per key), and misses sharing an s or t
        build through ``sharing.build_member_indexes``'s common edge
        arena.
        """
        resolved: Dict[QueryKey, Tuple[LightweightIndex, bool]] = {}
        missing: List[QueryKey] = []
        for key in keys:
            if key in resolved:
                # duplicate occurrence shares the resolved (or in-flight)
                # build — that's a cache hit: no rebuild happens for it.
                # The tenant counter moves with the global one, or
                # per-tenant stats drift from the global delta
                # (BatchServeReport.tenant_cache under-reports)
                self.cache.stats.hits += 1
                self.cache.stats_for(tenant_of(key)).hits += 1
                continue
            idx = self.cache.get(key)
            if idx is not None:
                resolved[key] = (idx, True)
            else:
                resolved[key] = (None, False)  # type: ignore[assignment]
                missing.append(key)

        if not missing:
            return resolved

        dists: Dict[QueryKey, Tuple[np.ndarray, np.ndarray]] = {}
        kept: Dict[QueryKey, Optional[np.ndarray]] = {}
        if precomputed:
            dists.update({k: precomputed[k] for k in missing
                          if k in precomputed})
        unmasked = [k for k in missing if k[4] == 0 and k not in dists]
        if unmasked:
            on_device = (resolve_backend(None, self.engine.backend)
                         == "device" and bfs.fits_device(
                             graph.n, max(key[3] for key in unmasked)))
            with trace.span("pathenum.index.bfs") as sp:
                dists.update(self._stacked_dists(graph, unmasked,
                                                 group_builds, on_device,
                                                 kept))
            timing.distance_seconds += sp.seconds

        build_graph = graph
        eff_mask = edge_mask
        if group_builds and edge_mask is not None and len(missing) > 1:
            # one filtered graph serves every masked miss; building on it
            # (mask dropped) is byte-identical to the per-key masked
            # build, which constructs exactly this graph internally
            with trace.span("pathenum.index.bfs") as sp:
                keep = np.asarray(edge_mask, dtype=bool)
                edges = np.stack([graph.esrc[keep], graph.edst[keep]],
                                 axis=1)
                build_graph = from_edges(graph.n, edges, dedup=False)
                eff_mask = None
                masked_missing = [kk for kk in missing if kk not in dists]
                if masked_missing:
                    dists.update(self._stacked_dists(
                        build_graph, masked_missing, group_builds, False,
                        kept))
            timing.distance_seconds += sp.seconds

        with trace.span("pathenum.index.build") as sp:
            built: Dict[QueryKey, LightweightIndex] = {}
            if group_builds:
                groupable = [kk for kk in missing
                             if kk in dists and kept.get(kk) is None]
                for grp in sharing_mod.detect_groups(groupable):
                    idxs = sharing_mod.build_member_indexes(
                        build_graph,
                        [(kk[1], kk[2], kk[3]) for kk in grp.keys],
                        [dists[kk] for kk in grp.keys])
                    built.update(zip(grp.keys, idxs))

            for key in missing:
                _, s, t, k, _mh, _gv = key
                if key in built:
                    idx = built[key]
                elif key in dists:
                    # the mask still threads through: build_index must
                    # filter the edge set even when the distances are
                    # precomputed, or masked-out edges leak into the
                    # index (the distances themselves are the caller's
                    # contract — computed on the same filtered graph)
                    d_s, d_t = dists[key]
                    idx = build_index(build_graph, s, t, k,
                                      dist_fn=lambda *_a, _d=(d_s, d_t): _d,
                                      edge_mask=eff_mask,
                                      kept=kept.get(key))
                else:  # masked query — BFS must run on the filtered graph
                    idx = build_index(build_graph, s, t, k,
                                      edge_mask=eff_mask)
                self.cache.put(key, idx)
                resolved[key] = (idx, False)
        timing.index_seconds += sp.seconds
        return resolved

    def _stacked_dists(self, graph: Graph, keys: List[QueryKey],
                       dedup_pairs: bool, on_device: bool,
                       kept: Dict[QueryKey, Optional[np.ndarray]]
                       ) -> Dict[QueryKey, Tuple[np.ndarray, np.ndarray]]:
        """Stacked BFS for a list of distinct keys, on the device
        (``bfs.stacked_index_inputs``) or the host
        (``batched_index_distances``).  On the device each row also
        lists the edges its index keeps, which go into ``kept`` for the
        keys whose budget is the row's.

        With ``dedup_pairs`` (sharing enabled, DESIGN.md §13) the BFS runs
        one row per distinct ``(s, t)`` *pair* at the pair's max hop
        budget, then clips each key's copy to its own ``k + 1`` sentinel.
        That is byte-identical to the per-key rows — the stacked
        relaxation already runs every row to the block's max k and clips,
        so values ≤ k match the bounded queue BFS exactly and everything
        beyond collapses onto the same sentinel — but it collapses the
        hot Zipfian case exact-key dedup cannot touch: the same pair
        queried under many hop budgets pays for one BFS pair, not one
        per budget.
        """
        dist_fn = (bfs.stacked_index_inputs if on_device
                   else batched_index_distances)
        if not dedup_pairs:
            stacked = dist_fn(
                graph, [(s, t, k) for (_, s, t, k, _, _) in keys],
                block=self.bfs_block)
            if on_device:
                kept.update((key, row[2]) for key, row in zip(keys, stacked))
            return {key: row[:2] for key, row in zip(keys, stacked)}
        pair_k: Dict[Tuple[int, int], int] = {}
        for (_, s, t, k, _mh, _gv) in keys:
            pair_k[(s, t)] = max(pair_k.get((s, t), 0), k)
        pairs = list(pair_k)
        stacked = dist_fn(
            graph, [(s, t, pair_k[(s, t)]) for (s, t) in pairs],
            block=self.bfs_block)
        by_pair = dict(zip(pairs, stacked))
        out: Dict[QueryKey, Tuple[np.ndarray, np.ndarray]] = {}
        for key in keys:
            _, s, t, k, _mh, _gv = key
            d_s, d_t, *ids = by_pair[(s, t)]
            if k == pair_k[(s, t)]:      # the row's own budget: as it is
                out[key] = (d_s, d_t)
                if ids:
                    kept[key] = ids[0]
                continue
            out[key] = (np.minimum(d_s, k + 1).astype(np.int32),
                        np.minimum(d_t, k + 1).astype(np.int32))
        return out

    # -- planning -----------------------------------------------------------
    def _plan_for(self, idx: LightweightIndex, k: int, mode: str) -> Plan:
        """One distinct query's plan under the batch ``mode`` knob.  The
        engine backend steers where the full DP runs (join.hop_count_dp,
        DESIGN.md §9); the plan itself is backend-independent."""
        if mode == "auto":
            return planner_mod.plan_query(idx, tau=self.engine.tau,
                                          backend=self.engine.backend)
        if mode == "dfs":
            return Plan(method="dfs", cut=None, preliminary=-1.0,
                        used_full_estimator=False)
        if mode == "join":
            dp_plan = planner_mod.plan_query(idx, tau=-1.0,
                                             backend=self.engine.backend)
            cut = dp_plan.cut if dp_plan.cut else max(1, k // 2)
            return Plan(method="join", cut=cut, preliminary=-1.0,
                        used_full_estimator=True,
                        optimize_seconds=dp_plan.optimize_seconds)
        raise ValueError(f"unknown mode {mode!r}")

    # -- enumeration --------------------------------------------------------
    def _enumerate(self, idx: LightweightIndex, plan: Plan, count_only: bool,
                   first_n: Optional[int], deadline: Optional[float],
                   order: Optional[str] = None,
                   weights: Optional[np.ndarray] = None) -> EnumResult:
        if plan.method == "dfs":
            return enumerate_paths_idx(idx, chunk_size=self.engine.chunk_size,
                                       count_only=count_only, first_n=first_n,
                                       deadline=deadline,
                                       backend=self.engine.backend,
                                       order=order, weights=weights)
        return enumerate_paths_join(idx, cut=plan.cut, count_only=count_only,
                                    first_n=first_n,
                                    max_partials=self.engine.max_partials,
                                    deadline=deadline,
                                    order=order, weights=weights)

    def run(self, graph: Graph, queries: Sequence[Tuple[int, int, int]],
            count_only: bool = True, first_n: Optional[int] = None,
            mode: str = "auto", edge_mask: Optional[np.ndarray] = None,
            deadline: Optional[float] = None,
            graph_id: str = DEFAULT_GRAPH_ID,
            order: Optional[str] = None,
            weights: Optional[np.ndarray] = None,
            sharing: Optional[str] = None,
            _precomputed_distances: Optional[Dict[QueryKey, Tuple[np.ndarray,
                                                                  np.ndarray]]] = None,
            ) -> BatchOutput:
        """Serve a batch; returns per-query items in input order.

        ``sharing`` overrides the engine's cross-query sharing knob for
        this run (DESIGN.md §13): ``"auto"`` detects overlap groups
        (shared s/t under this run's graph/mask/version), builds merged
        group indexes and walks shared prefixes once; ``"off"`` pins the
        per-query pipeline.  Results are byte-identical either way —
        sharing only changes *where* the work happens, and unprofitable
        or unsafe groups (ranked batches, over-budget walks) fall back
        to the solo path automatically.  ``REPRO_SHARING=off`` in the
        environment force-disables it regardless of this argument.

        ``order`` requests ranked (any-k) enumeration for the whole batch
        (DESIGN.md §10): each query's paths come back in non-decreasing
        hop/weight rank with the lexicographic tie-break, ``first_n``
        means the per-query top-n, and a ``deadline`` truncation is a
        rank-optimal prefix per query.  ``weights`` (graph edge order,
        non-negative) feeds ``order="weight"``.

        ``graph_id`` names the tenant ``graph`` belongs to (DESIGN.md §8):
        it prefixes every cache key this run touches, so two tenants'
        identical ``(s, t, k)`` queries never share an index entry.  All
        queries of one ``run`` are against one graph — multi-tenant
        callers group by ``graph_id`` first (serving/hcpe.group_requests)
        and run one batch per group.  The default id keeps single-graph
        callers on the exact pre-tenancy behavior.

        ``deadline`` (absolute ``core.clock.now()``) is the batch's
        cooperative stop: enumeration halts at the next chunk boundary
        after it passes, queries not yet enumerated return empty with
        ``exhausted=False``, and everything already emitted is kept.  The
        index/planner phases are not interrupted (they are the cheap,
        bounded part of the pipeline); only chunked enumeration — where
        the unbounded work lives — honors the budget.

        ``_precomputed_distances`` is the distributed hand-off: the mesh BFS
        of distributed/engine.py injects (dist_s, dist_t) per key so the
        host build skips its own distance passes.  Keys are full
        ``QueryKey`` tuples — including ``edge_mask_hash`` and
        ``graph.version`` — and for masked keys the distances must have
        been computed on the same filtered graph (the mask still filters
        the index build; only the BFS is skipped).
        """
        t_batch = time.perf_counter()
        with trace.span("pathenum.batch"):
            out = self._run(graph, queries, count_only, first_n, mode,
                            edge_mask, deadline, graph_id, order, weights,
                            sharing, _precomputed_distances)
        out.timing.started_at = t_batch
        out.timing.ended_at = time.perf_counter()
        out.timing.total_seconds = out.timing.ended_at - t_batch
        return out

    def _run(self, graph: Graph, queries: Sequence[Tuple[int, int, int]],
             count_only: bool, first_n: Optional[int], mode: str,
             edge_mask: Optional[np.ndarray], deadline: Optional[float],
             graph_id: str, order: Optional[str],
             weights: Optional[np.ndarray], sharing: Optional[str],
             precomputed: Optional[Dict[QueryKey, Tuple[np.ndarray,
                                                        np.ndarray]]],
             ) -> BatchOutput:
        """`run`'s phases: indexes, plans, then the shared, fused and
        solo enumerations, each timed by its span (DESIGN.md §12)."""
        timing = BatchTiming()
        stats_before = self.cache.stats.snapshot()
        for (s, t, k) in queries:
            if k < 2:
                raise ValueError("paper assumes k >= 2")
            if s == t:
                raise ValueError("s and t must be distinct")
        mh = edge_mask_hash(edge_mask)
        gv = int(graph.version)
        keys = [(graph_id, int(s), int(t), int(k), mh, gv)
                for (s, t, k) in queries]
        distinct = list(dict.fromkeys(keys))
        eff_sharing: str = sharing_mod.resolve_sharing(
            self.sharing if sharing is None else sharing)

        resolved = self._indexes_for(graph, keys, edge_mask, precomputed,
                                     timing,
                                     group_builds=eff_sharing == "auto")

        plans: Dict[QueryKey, Plan] = {}

        def plan_all(todo: List[QueryKey]) -> None:
            for key in todo:
                if key not in plans:
                    plans[key] = self._plan_for(resolved[key][0], key[3],
                                                mode)
                    timing.optimize_seconds += plans[key].optimize_seconds

        # sharing phase (DESIGN.md §13): plan the distinct keys up front,
        # then serve whole overlap groups off one shared prefix walk.
        # Ranked batches opt out — their drivers emit in rank order, which
        # a shared walk does not reproduce — and keep Level-A (construction)
        # sharing only.
        shared_results: Dict[QueryKey, EnumResult] = {}
        shared_latency: Dict[QueryKey, float] = {}
        n_groups = 0
        if eff_sharing == "auto" and order is None:
            plan_all(distinct)
            if len(plans) > 1:
                with trace.span("pathenum.enum.shared") as sp:
                    shared_results, shared_latency, n_groups = \
                        sharing_mod.run_shared_groups(
                            self, resolved, plans, count_only=count_only,
                            first_n=first_n, deadline=deadline,
                            graph_id=graph_id)
                timing.enumerate_seconds += sp.seconds

        # fused device phase (DESIGN.md §9): the remaining dfs-plan
        # queries that resolve to the device backend enumerate together
        # through fused multi-query launches — one kernel dispatch per
        # expansion round for the whole micro-batch instead of one
        # dispatch stream per query.  Shared-walk results, join plans,
        # ranked batches and host-resolved queries keep the solo path.
        fused_results: Dict[QueryKey, EnumResult] = {}
        fused_latency: Dict[QueryKey, float] = {}
        fused_dispatches = 0
        if (order is None and self.fused != "off"
                and self.engine.backend in ("device", "auto")):
            from ..kernels import ops as kops   # lazy: pallas path only
            from . import fused as fused_mod
            from .enumerate import resolve_backend
            plan_all(distinct)
            elig = [kk for kk in distinct
                    if kk not in shared_results
                    and plans[kk].method == "dfs"
                    and resolve_backend(resolved[kk][0],
                                        self.engine.backend) == "device"]
            if len(elig) >= 2:
                before = kops.device_dispatch_count()
                with trace.span("pathenum.enum.fused") as sp:
                    res_list = fused_mod.enumerate_fused_device(
                        [resolved[kk][0] for kk in elig],
                        chunk_size=self.engine.chunk_size,
                        count_only=count_only, first_n=first_n,
                        deadline=deadline)
                fused_dispatches = kops.device_dispatch_count() - before
                timing.enumerate_seconds += sp.seconds
                fused_results = dict(zip(elig, res_list))
                share = sp.seconds / len(elig)
                fused_latency = {kk: share for kk in elig}

        # solo phase: every distinct query no shared walk or fused launch
        # served runs its own pipeline, in first-occurrence order
        solo = [kk for kk in distinct
                if kk not in shared_results and kk not in fused_results]
        plan_all(solo)
        solo_results: Dict[QueryKey, EnumResult] = {}
        solo_latency: Dict[QueryKey, float] = {}
        if solo:
            with trace.span("pathenum.enum.solo") as sp:
                for key in solo:
                    t1 = time.perf_counter()
                    solo_results[key] = self._enumerate(
                        resolved[key][0], plans[key], count_only, first_n,
                        deadline, order=order, weights=weights)
                    solo_latency[key] = time.perf_counter() - t1
            timing.enumerate_seconds += sp.seconds

        # a query's attributable work: its plan plus its enumeration (a
        # fused launch's wall split evenly over its members); a duplicate
        # reuses its twin's result and adds none
        items: List[BatchItem] = []
        memo: Dict[QueryKey, BatchItem] = {}
        for key in keys:
            prior = memo.get(key)
            if prior is not None:
                items.append(dataclasses.replace(
                    prior, deduplicated=True, index_cached=True,
                    latency_seconds=0.0))
                continue
            idx, was_cached = resolved[key]
            plan = plans[key]
            if key in shared_results:
                res, wall = shared_results[key], shared_latency[key]
            elif key in fused_results:
                res, wall = fused_results[key], fused_latency[key]
            else:
                res, wall = solo_results[key], solo_latency[key]
            item = BatchItem(s=key[1], t=key[2], k=key[3], result=res,
                             plan=plan, index_cached=was_cached,
                             deduplicated=False,
                             latency_seconds=wall + plan.optimize_seconds,
                             shared=key in shared_results,
                             fused=key in fused_results)
            memo[key] = item
            items.append(item)

        return BatchOutput(items=items, timing=timing,
                           cache_stats=self.cache.stats.delta(stats_before),
                           distinct_queries=len(memo), graph_id=graph_id,
                           sharing_groups=n_groups,
                           shared_queries=len(shared_results),
                           fused_queries=len(fused_results),
                           fused_dispatches=fused_dispatches)

    def counts(self, graph: Graph, queries: Sequence[Tuple[int, int, int]],
               **kw) -> np.ndarray:
        """Convenience: ``run(..., count_only=True)`` reduced to the
        per-query count vector."""
        return self.run(graph, queries, count_only=True, **kw).counts
