"""Fused multi-query device enumeration (DESIGN.md §9).

The batch engine's device path used to run one query at a time: each
query's chunk walk issued its own sequence of kernel dispatches, so an
async micro-batch of N device-eligible queries paid N dispatch streams.
This driver packs the frontier walks of many queries into *fused
launches*: every expansion round pops one chunk from each active
query's LIFO deque, tags the rows with the query's slot, and expands
them all through ONE ``ops.frontier_expand_fused`` dispatch
(tests/test_fused_launch.py asserts the launch count).  The members'
offset tables and adjacency slabs are stacked once per run
(``ops.fused_tables``), each member at a fixed slot; a round sends only
its rows and per-slot vectors, and its launch selects each member's
budget column itself.

Per-query semantics are `core.enumerate._drive`'s, replicated exactly:

  * each query owns its own LIFO work deque, popped in the same order
    as a solo run (rounds interleave queries, but one query's chunk
    sequence — and therefore its ``stats.chunks``, emission blocks and
    ``first_n`` prefix — is untouched by its co-tenants);
  * the zero-fanout host shortcut, chunk_size splitting with reversed
    pushes, per-chunk ``first_n`` trim, canonical exhausted sort and
    the cooperative deadline all match the solo driver;
  * Fig.-6 counters come back as per-member rows of the fused kernel's
    (m, 4) counter matrix, bit-identical to each query's solo run.

Queries with constraints, ranked order or a non-dfs plan never reach
this module — `core.batch.BatchPathEnum` gates eligibility and falls
back to the solo per-query path (DESIGN.md §9 fallback matrix).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from .. import trace
from . import clock
from .enumerate import (DEVICE_SLOT_BUDGET, DRIVER, EnumResult, EnumStats,
                        _count_d2h, _fanout_segments, _finalize,
                        _trim_to_first_n)
from .graph import PAD
from .index import LightweightIndex


class _MemberState:
    """One query's private driver state inside a fused run."""
    __slots__ = ("idx", "dev", "stats", "out_paths", "out_lens", "count",
                 "work", "result")

    def __init__(self, idx: LightweightIndex) -> None:
        self.idx = idx
        self.dev = idx.device_arrays()
        self.stats = EnumStats()
        self.out_paths: List[np.ndarray] = []
        self.out_lens: List[np.ndarray] = []
        self.count = 0
        root = np.full((1, idx.k + 1), PAD, dtype=np.int32)
        root[0, 0] = idx.s
        self.work: List[Tuple[np.ndarray, int]] = [(root, 0)]
        self.result: Optional[EnumResult] = None

    def finish(self, exhausted: bool, canonical: bool = False) -> None:
        self.result = _finalize(self.idx, self.out_paths, self.out_lens,
                                self.count, self.stats, exhausted=exhausted,
                                canonical=canonical)


def enumerate_fused_device(
    indexes: List[LightweightIndex],
    chunk_size: int = 16384,
    count_only: bool = False,
    first_n: Optional[int] = None,
    deadline: Optional[float] = None,
) -> List[EnumResult]:
    """Enumerate many queries' P(s,t,k,G) through fused device launches.

    Returns one ``EnumResult`` per index, in input order, each
    byte-identical (paths, count, stats, chunk accounting) to a solo
    ``enumerate_paths_idx(idx, backend="device")`` run — the fusion
    changes dispatch granularity, never per-query semantics.  All
    indexes must come from one graph (equal ``n``).  ``first_n`` is
    per-query (each member trims and finishes independently); the
    ``deadline`` (absolute ``core.clock.now()``) is checked once per
    fused round, finalizing every unfinished member with
    ``exhausted=False``.
    """
    from ..kernels import ops as kops   # lazy: pallas only on this path
    if not indexes:
        return []
    n = indexes[0].n
    if any(ix.n != n for ix in indexes):
        raise ValueError("fused launches require one common graph")
    states = [_MemberState(ix) for ix in indexes]
    trace.count(DRIVER + "fused", len(states))
    k1max = max(ix.k for ix in indexes) + 1
    # member i keeps slot i for the whole run: the tables are built
    # once, and a finished member's slot stays, with no row pointing at it
    slots = kops._next_pow2(len(states))
    with trace.span("pathenum.enum.tables"):
        begin_all, end_all, dst_all = kops.fused_tables(
            [st.dev.begin for st in states], [st.dev.end for st in states],
            [st.dev.dst for st in states], slots=slots)
        trace.count("pathenum.enum.table_builds")
        trace.count("pathenum.enum.table_bytes",
                    begin_all.nbytes + end_all.nbytes + dst_all.nbytes)

    # each round is spanned phase by phase (pop, pack, then dispatch,
    # sync and split per launch, tail), so the phases cover it
    while True:
        with trace.span("pathenum.enum.pop"):
            active = [(slot, st) for slot, st in enumerate(states)
                      if st.result is None]
            if not active:
                break
            if deadline is not None and clock.expired(deadline):
                for _slot, st in active:
                    st.finish(exhausted=False)
                break
            trace.count("pathenum.enum.rounds")
            # pop one chunk per active member; the host zero-fanout
            # shortcut (solo: _device_step returns None without a
            # launch) keeps dead chunks out of the dispatch entirely
            members: List[Tuple[int, _MemberState, np.ndarray, int,
                                np.ndarray]] = []
            for slot, st in active:
                paths, depth = st.work.pop()
                st.stats.chunks += 1
                k = st.idx.k
                last = paths[:, depth].astype(np.int64)
                b = k - depth - 1
                cnt = st.idx.it_count(last, b)
                if int(cnt.sum()) == 0:
                    st.stats.invalid_partials += paths.shape[0]
                    if not st.work:
                        st.finish(exhausted=True, canonical=True)
                    continue
                members.append((slot, st, paths, depth, cnt))
        if not members:
            continue

        with trace.span("pathenum.enum.pack"):
            tvec = np.full(slots, -1, np.int32)
            depthv = np.zeros(slots, np.int32)
            bvec = np.zeros(slots, np.int32)
            wantc = np.zeros(slots, bool)
            packed, ranks, cnts = [], [], []
            for slot, st, paths, depth, cnt in members:
                k = st.idx.k
                tvec[slot] = st.idx.t
                depthv[slot] = depth
                bvec[slot] = k - depth - 1
                wantc[slot] = depth + 1 < k
                if paths.shape[1] < k1max:
                    paths = np.pad(paths,
                                   ((0, 0), (0, k1max - paths.shape[1])),
                                   constant_values=PAD)
                packed.append(paths)
                ranks.append(np.full(paths.shape[0], slot, np.int32))
                cnts.append(cnt)
            packed_paths = np.concatenate(packed, axis=0)
            rank = np.concatenate(ranks)
            packed_cnt = np.concatenate(cnts)
            # the solo path's slot-budget segmentation, over the packed
            # rows: a hub member splits the round into several dispatches
            # exactly as it would have split its own solo chunk
            segments = _fanout_segments(packed_cnt, DEVICE_SLOT_BUDGET)

        emit_parts: List[List[np.ndarray]] = [[] for _ in members]
        cont_parts: List[List[np.ndarray]] = [[] for _ in members]
        for lo, hi in segments:
            with trace.span("pathenum.enum.dispatch"):
                out = kops.frontier_expand_fused(
                    packed_paths[lo:hi], rank[lo:hi], tvec, depthv,
                    begin_all, end_all, bvec, dst_all, wantc,
                    max_deg=max(int(packed_cnt[lo:hi].max()), 1))
            with trace.span("pathenum.enum.sync"):
                emit_np, cont_np, ne_m, nc_m, ctr = (np.asarray(a)
                                                     for a in out)
            # the launch copies back whole padded rectangles; only the
            # first n_emit + n_cont rows of them are live
            live_rows = int(ne_m.sum()) + int(nc_m.sum())
            _count_d2h(sum(a.nbytes for a in (emit_np, cont_np, ne_m, nc_m,
                                              ctr)),
                       live=live_rows * emit_np.shape[1] * 4
                       + ne_m.nbytes + nc_m.nbytes + ctr.nbytes)
            with trace.span("pathenum.enum.split"):
                ne_m = ne_m.astype(np.int64)
                nc_m = nc_m.astype(np.int64)
                e_lo = np.concatenate([[0], np.cumsum(ne_m)[:-1]])
                c_lo = np.concatenate([[0], np.cumsum(nc_m)[:-1]])
                for i, (slot, st, *_) in enumerate(members):
                    st.stats.edges_accessed += int(ctr[slot, 0])
                    st.stats.partials_generated += int(ctr[slot, 1])
                    st.stats.invalid_partials += int(ctr[slot, 2])
                    w = st.idx.k + 1
                    if ne_m[slot]:
                        emit_parts[i].append(
                            emit_np[e_lo[slot]:e_lo[slot] + ne_m[slot], :w])
                    if nc_m[slot]:
                        cont_parts[i].append(
                            cont_np[c_lo[slot]:c_lo[slot] + nc_m[slot], :w])

        # per-member driver tail — the exact _drive emit/push sequence
        with trace.span("pathenum.enum.tail"):
            for i, (_slot, st, _paths, depth, _cnt) in enumerate(members):
                if emit_parts[i]:
                    emit_cat = np.concatenate(emit_parts[i], axis=0)
                    st.count += emit_cat.shape[0]
                    st.stats.results += emit_cat.shape[0]
                    if not count_only:
                        st.out_paths.append(emit_cat)
                        st.out_lens.append(np.full(emit_cat.shape[0],
                                                   depth + 1, np.int32))
                    if first_n is not None and st.count >= first_n:
                        st.count = _trim_to_first_n(
                            st.out_paths, st.out_lens, st.count, first_n,
                            count_only, st.stats)
                        st.finish(exhausted=False)
                        continue
                if cont_parts[i]:
                    cont_cat = np.concatenate(cont_parts[i], axis=0)
                    pieces = range(0, cont_cat.shape[0], chunk_size)
                    for piece in reversed(list(pieces)):
                        st.work.append(
                            (cont_cat[piece:piece + chunk_size], depth + 1))
                if not st.work:
                    st.finish(exhausted=True, canonical=True)

    return [st.result for st in states]  # type: ignore[misc]
