"""Fused multi-query device enumeration (DESIGN.md §9).

The batch engine's device path used to run one query at a time: each
query's chunk walk issued its own sequence of kernel dispatches, so an
async micro-batch of N device-eligible queries paid N dispatch streams.
This driver packs the frontier walks of many queries into *fused
launches*: every expansion round pops one chunk from each active
query's LIFO deque, tags the rows with the query's member rank, and
expands them all through ONE ``ops.frontier_expand_fused`` dispatch
(tests/test_fused_launch.py asserts the launch count).

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

from . import clock
from .enumerate import (DEVICE_SLOT_BUDGET, DRIVER_RUNS, EnumResult,
                        EnumStats, _fanout_segments, _finalize,
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


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length() if x > 1 else 1


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
    import jax.numpy as jnp
    if not indexes:
        return []
    n = indexes[0].n
    if any(ix.n != n for ix in indexes):
        raise ValueError("fused launches require one common graph")
    states = [_MemberState(ix) for ix in indexes]
    DRIVER_RUNS["fused"] += len(states)
    k1max = max(ix.k for ix in indexes) + 1
    mfm = _next_pow2(max(int(st.dev.dst.shape[0]) for st in states))

    while True:
        active = [st for st in states if st.result is None]
        if not active:
            break
        if deadline is not None and clock.expired(deadline):
            for st in active:
                st.finish(exhausted=False)
            break

        # pop one chunk per active member; the host zero-fanout shortcut
        # (solo: _device_step returns None without a launch) keeps dead
        # chunks out of the dispatch entirely
        members: List[Tuple[_MemberState, np.ndarray, int, np.ndarray]] = []
        for st in active:
            paths, depth = st.work.pop()
            st.stats.chunks += 1
            k = st.idx.k
            last = paths[:, depth].astype(np.int64)
            b = k - depth - 1
            cnt = (st.idx.fwd_end[last, b] - st.idx.fwd_begin[last]) \
                if b >= 0 else np.zeros(paths.shape[0], np.int64)
            if int(cnt.sum()) == 0:
                st.stats.invalid_partials += paths.shape[0]
                if not st.work:
                    st.finish(exhausted=True, canonical=True)
                continue
            members.append((st, paths, depth, cnt))
        if not members:
            continue

        packed, ranks, cnts = [], [], []
        for i, (st, paths, depth, cnt) in enumerate(members):
            if paths.shape[1] < k1max:
                paths = np.pad(paths,
                               ((0, 0), (0, k1max - paths.shape[1])),
                               constant_values=PAD)
            packed.append(paths)
            ranks.append(np.full(paths.shape[0], i, np.int32))
            cnts.append(cnt)
        packed_paths = np.concatenate(packed, axis=0)
        rank = np.concatenate(ranks)
        packed_cnt = np.concatenate(cnts)

        m = _next_pow2(len(members))
        tvec = np.full(m, -1, np.int32)
        depthv = np.zeros(m, np.int32)
        wantc = np.zeros(m, bool)
        begin_parts: List[object] = []
        endb_parts: List[object] = []
        dst_parts: List[object] = []
        for i, (st, _paths, depth, _cnt) in enumerate(members):
            k = st.idx.k
            tvec[i] = st.idx.t
            depthv[i] = depth
            wantc[i] = depth + 1 < k
            begin_parts.append(st.dev.begin)
            endb_parts.append(st.dev.end[:, k - depth - 1])
            mf = int(st.dev.dst.shape[0])
            dst_parts.append(jnp.pad(st.dev.dst, (0, mfm - mf),
                                     constant_values=PAD)
                             if mf < mfm else st.dev.dst)
        zero_col = jnp.zeros((n,), jnp.int32)
        pad_dst = jnp.full((mfm,), PAD, jnp.int32)
        for _ in range(m - len(members)):
            begin_parts.append(zero_col)
            endb_parts.append(zero_col)
            dst_parts.append(pad_dst)
        begin_flat = jnp.concatenate(begin_parts)
        endb_flat = jnp.concatenate(endb_parts)
        dst_flat = jnp.concatenate(dst_parts)

        # the solo path's slot-budget segmentation, over the packed rows:
        # a hub member splits the round into several dispatches exactly
        # as it would have split its own solo chunk
        emit_parts: List[List[np.ndarray]] = [[] for _ in members]
        cont_parts: List[List[np.ndarray]] = [[] for _ in members]
        for lo, hi in _fanout_segments(packed_cnt, DEVICE_SLOT_BUDGET):
            emit_rows, cont_rows, n_emit_m, n_cont_m, counters = \
                kops.frontier_expand_fused(
                    packed_paths[lo:hi], rank[lo:hi], tvec, depthv,
                    begin_flat, endb_flat, dst_flat, wantc,
                    max_deg=max(int(packed_cnt[lo:hi].max()), 1))
            ne_m = np.asarray(n_emit_m).astype(np.int64)
            nc_m = np.asarray(n_cont_m).astype(np.int64)
            ctr = np.asarray(counters)
            e_lo = np.concatenate([[0], np.cumsum(ne_m)[:-1]])
            c_lo = np.concatenate([[0], np.cumsum(nc_m)[:-1]])
            emit_np = np.asarray(emit_rows)
            cont_np = np.asarray(cont_rows)
            for i, (st, _paths, _depth, _cnt) in enumerate(members):
                st.stats.edges_accessed += int(ctr[i, 0])
                st.stats.partials_generated += int(ctr[i, 1])
                st.stats.invalid_partials += int(ctr[i, 2])
                w = st.idx.k + 1
                if ne_m[i]:
                    emit_parts[i].append(
                        emit_np[e_lo[i]:e_lo[i] + ne_m[i], :w])
                if nc_m[i]:
                    cont_parts[i].append(
                        cont_np[c_lo[i]:c_lo[i] + nc_m[i], :w])

        # per-member driver tail — the exact _drive emit/push sequence
        for i, (st, _paths, depth, _cnt) in enumerate(members):
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
