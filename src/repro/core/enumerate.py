"""IDX-DFS adapted to frontiers (Algorithm 4 → chunked level-synchronous).

The recursive DFS of the paper becomes a *chunked depth-first frontier*
walk: partial results are rows of a fixed-width int32 matrix, one hop
expands every row of a chunk simultaneously (gather from the index via the
O(1) offset lookup), and a LIFO deque of chunks preserves the depth-first
memory bound — the live set is O(chunk · k · max_branch/chunk) rather than
the paper's O(k), the standard accelerator transformation (DESIGN.md §2).

Semantics are identical to Algorithm 4:
  * candidates come from I_t(v, k - L(M) - 1)   (budget read off the index)
  * the simple-path check `v' ∉ M` is the vectorized prefix compare
  * a row reaching t is emitted

Instrumentation mirrors the paper's Fig. 6 metrics: #edges accessed,
#invalid partials (generated partials that never reach any result — here:
dup-pruned expansions plus dead-end rows), #results.

Two expansion backends share this driver loop (DESIGN.md §9): ``host``
runs `_expand_chunk` in numpy; ``device`` runs the same hop as a Pallas
kernel (kernels/frontier_expand, via kernels/ops.frontier_expand) over
fixed-width PAD-padded chunks, with the Fig.-6 counters coming back as
device scalars.  ``auto`` picks the device for small k and dense
frontiers and falls back to the host otherwise (`resolve_backend`).
Results, stats and chunk boundaries are bit-identical across backends.

Ranked (any-k) enumeration (DESIGN.md §10): ``order="hops"|"weight"``
replaces the LIFO chunk walk with a priority-ordered frontier.  The host
runs a best-first heap over partial-path lower bounds (`_drive_ranked_heap`
— bound = accumulated cost + the index's distance-to-t array, or its
min-plus weighted analogue from rank.py); the device path runs
rank-bucketed chunk scheduling (`_drive_ranked_buckets`) that drains one
integer hop-bound bucket at a time through the *unchanged* Pallas kernel.
Both emit paths in non-decreasing ``(cost, lexicographic sequence)``
order, so ``first_n`` returns the top-n and a deadline truncation is a
rank-optimal prefix.  With ``order=None``, exhausted results are
canonicalized to the same key, so every backend/plan returns the same
ordered list on a full enumeration.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import os
from typing import List, Optional, Tuple

import numpy as np

from . import clock, rank
from .graph import PAD
from .index import LightweightIndex

# Auto-selection rule for backend="auto" (DESIGN.md §9): the device wins
# when chunks are wide (dense frontiers — many index edges feeding each
# hop) and the path matrix is narrow (small k keeps the fixed-width
# layout and the prefix compare cheap).  On CPU the kernel only runs in
# interpret mode, so auto never picks it there unless forced for CI
# (REPRO_DEVICE_ENUM=force).
DEVICE_AUTO_MAX_K = 8
DEVICE_AUTO_MIN_EDGES = 2048


# Monotone count of enumeration runs per driver: "host", "device_loop"
# (the host-looped device driver), "resident" (the device-resident
# deque), "resident_stall" (a resident run that finished on the
# host-looped driver) and "fused" (one per query of a fused launch).
# Callers read deltas to see which driver served a query.
DRIVER_RUNS: collections.Counter = collections.Counter()


def resolve_backend(idx: LightweightIndex, backend: Optional[str],
                    constraint=None, order: Optional[str] = None) -> str:
    """Resolve a requested backend to the one that will run (DESIGN.md §9
    fallback matrix).  Constraints are host-only state machines, so any
    constrained query runs on the host; ``order="weight"`` likewise runs
    on the host (float rank buckets don't exist — the device scheduler
    drains integer hop buckets, DESIGN.md §10); ``auto`` additionally
    requires small k, a dense-enough index, and a real accelerator (or
    ``REPRO_DEVICE_ENUM=force``, which lets CPU CI cover the device leg
    in interpret mode).  ``REPRO_DEVICE_ENUM=off|0`` is the uniform kill
    switch (same spelling as ``REPRO_SHARING`` / ``REPRO_PALLAS``): every
    query runs on the host, including explicit ``backend="device"``
    requests — the operator escape hatch when a device path misbehaves
    in production."""
    if backend is not None and backend not in ("host", "device", "auto"):
        raise ValueError(f"unknown backend {backend!r}")
    if os.environ.get("REPRO_DEVICE_ENUM", "").lower() in ("off", "0"):
        return "host"
    if backend is None or backend == "host":
        return "host"
    if constraint is not None:
        return "host"
    if order == "weight":
        return "host"
    if backend == "device":
        return "device"
    # backend == "auto"
    if idx.k > DEVICE_AUTO_MAX_K:
        return "host"
    if idx.num_index_edges < DEVICE_AUTO_MIN_EDGES:
        return "host"
    if os.environ.get("REPRO_DEVICE_ENUM") == "force":
        return "device"
    import jax
    return "device" if jax.default_backend() != "cpu" else "host"


class EngineLimit(RuntimeError):
    """Raised when a configured result/partial budget would be exceeded."""


@dataclasses.dataclass
class EnumStats:
    edges_accessed: int = 0
    invalid_partials: int = 0
    partials_generated: int = 0
    results: int = 0
    chunks: int = 0

    def merge(self, other: "EnumStats") -> None:
        self.edges_accessed += other.edges_accessed
        self.invalid_partials += other.invalid_partials
        self.partials_generated += other.partials_generated
        self.results += other.results
        self.chunks += other.chunks


@dataclasses.dataclass
class EnumResult:
    paths: np.ndarray          # (r, k+1) int32, PAD after the t column
    lengths: np.ndarray        # (r,) int32 — number of edges
    count: int                 # total results (== r unless count_only)
    stats: EnumStats
    exhausted: bool = True     # False when stopped early by first_n

    def as_tuples(self) -> List[Tuple[int, ...]]:
        out = []
        for row, l in zip(self.paths, self.lengths):
            out.append(tuple(int(x) for x in row[: l + 1]))
        return out


def _expand_chunk(idx: LightweightIndex, paths: np.ndarray, depth: int,
                  stats: EnumStats):
    """One hop for every row of `paths` (all at the same depth).

    Returns (emit_rows, cont_rows, parent_of_cont, parent_of_emit).
    """
    k, t = idx.k, idx.t
    last = paths[:, depth].astype(np.int64)
    b = k - depth - 1
    begin = idx.fwd_begin[last]
    end = idx.fwd_end[last, max(b, 0)] if b >= 0 else begin
    cnt = (end - begin).astype(np.int64)
    total = int(cnt.sum())
    stats.edges_accessed += total
    if total == 0:
        stats.invalid_partials += paths.shape[0]
        return None
    parent = np.repeat(np.arange(paths.shape[0], dtype=np.int64), cnt)
    offs = np.zeros(paths.shape[0], dtype=np.int64)
    np.cumsum(cnt[:-1], out=offs[1:])
    pos = np.arange(total, dtype=np.int64) - offs[parent] + begin[parent]
    vnew = idx.fwd_dst[pos].astype(np.int32)

    prefix = paths[parent, : depth + 1]
    dup = (prefix == vnew[:, None]).any(axis=1)
    is_t = vnew == t
    emit = is_t & ~dup
    cont = ~is_t & ~dup

    stats.partials_generated += total
    stats.invalid_partials += int(dup.sum())
    # rows whose every expansion died contribute to invalid partials
    alive = np.zeros(paths.shape[0], dtype=bool)
    alive[parent[emit | cont]] = True
    stats.invalid_partials += int((~alive).sum())
    return parent, pos, vnew, emit, cont


def enumerate_paths_idx(
    idx: LightweightIndex,
    chunk_size: int = 16384,
    count_only: bool = False,
    first_n: Optional[int] = None,
    max_results: Optional[int] = None,
    constraint=None,
    deadline: Optional[float] = None,
    backend: Optional[str] = None,
    order: Optional[str] = None,
    weights: Optional[np.ndarray] = None,
) -> EnumResult:
    """Enumerate P(s,t,k,G) from the light-weight index (Algorithm 4).

    ``constraint`` is an optional Appendix-E extension object (see
    constraints.py) carrying vectorized per-partial state.

    ``deadline`` is a cooperative chunk budget: an absolute
    ``core.clock.now()`` timestamp checked between chunks.  Once it
    passes, the results emitted so far come back with ``exhausted=False``
    — the anytime contract of ``first_n``, keyed on time instead of
    count.  Emitted results are never discarded, so the return value is
    always a correct (possibly partial) subset of the full result set.

    ``backend`` selects where frontier expansion runs (DESIGN.md §9):
    ``"host"``/None (numpy, the default), ``"device"`` (the Pallas
    frontier kernel; constrained queries fall back to the host), or
    ``"auto"`` (`resolve_backend`'s small-k/dense-frontier rule).  Both
    backends plug an expansion step into the one driver loop below, so
    paths, counts, ``EnumStats`` and chunk boundaries are identical by
    construction — only the expansion engine changes.

    ``order`` switches to ranked (any-k) enumeration (DESIGN.md §10):
    paths come back in non-decreasing rank — hop count or edge-weight
    sum (``weights``, graph edge order) — with lexicographic vertex
    sequences breaking ties, identically across backends.  ``first_n``
    then means the top-n and a deadline truncation is a rank-optimal
    prefix.  Ranked enumeration and ``constraint`` are mutually
    exclusive (the heap frontier carries rank state where the chunk
    walk carries constraint state).
    """
    spec = rank.make_rank_spec(order, weights)
    if spec is not None and constraint is not None:
        raise ValueError("order= cannot be combined with constraint= "
                         "(constrained ranked enumeration is not "
                         "supported; post-filter instead)")
    resolved = resolve_backend(idx, backend, constraint, order=order)
    if spec is None:
        if resolved == "device" and constraint is None \
                and first_n is None and max_results is None \
                and os.environ.get("REPRO_DEVICE_DEQUE", "").lower() \
                not in ("off", "0"):
            # full unconstrained device enumerations keep the work deque
            # resident on device (DESIGN.md §9); anytime contracts
            # (first_n / max_results) need per-chunk host decisions and
            # stay on the host-looped driver below
            return _drive_resident(idx, chunk_size=chunk_size,
                                   count_only=count_only,
                                   deadline=deadline)
        if resolved == "device":
            DRIVER_RUNS["device_loop"] += 1
            step = _device_step(idx)
        else:
            DRIVER_RUNS["host"] += 1
            step = _host_step(idx, constraint)
        return _drive(idx, step, chunk_size=chunk_size,
                      count_only=count_only, first_n=first_n,
                      max_results=max_results, constraint=constraint,
                      deadline=deadline)
    if resolved == "device":
        return _drive_ranked_buckets(idx, _device_step(idx),
                                     chunk_size=chunk_size,
                                     count_only=count_only, first_n=first_n,
                                     max_results=max_results,
                                     deadline=deadline)
    return _drive_ranked_heap(idx, spec, chunk_size=chunk_size,
                              count_only=count_only, first_n=first_n,
                              max_results=max_results, deadline=deadline)


def _drive(idx: LightweightIndex, step, chunk_size: int, count_only: bool,
           first_n: Optional[int], max_results: Optional[int], constraint,
           deadline: Optional[float]) -> EnumResult:
    """The backend-independent IDX-DFS driver (DESIGN.md §9).

    Owns every anytime contract — the LIFO chunk walk, the per-chunk
    deadline check, first_n's exact-n trim, the max_results limit, and
    chunk_size splitting — so host and device expansion cannot diverge
    on them.  ``step(paths, depth, cstate, stats, want_cont)`` performs
    one hop for one chunk and returns ``None`` (chunk fully dead, stats
    already updated) or ``(emit_rows, cont_rows, cont_state)`` with rows
    in emission order; ``want_cont`` is False on the last hop, where
    survivors could never be extended.
    """
    k, s = idx.k, idx.s
    root = np.full((1, k + 1), PAD, dtype=np.int32)
    root[0, 0] = s
    cstate0 = constraint.init(1) if constraint is not None else None
    # LIFO deque of (paths, depth, constraint_state) — deepest first = DFS
    work: List[Tuple[np.ndarray, int, object]] = [(root, 0, cstate0)]
    return _drive_from(idx, step, work, EnumStats(), [], [], 0,
                       chunk_size=chunk_size, count_only=count_only,
                       first_n=first_n, max_results=max_results,
                       constraint=constraint, deadline=deadline)


def _drive_from(idx: LightweightIndex, step,
                work: List[Tuple[np.ndarray, int, object]],
                stats: EnumStats, out_paths: List[np.ndarray],
                out_lens: List[np.ndarray], count: int, chunk_size: int,
                count_only: bool, first_n: Optional[int],
                max_results: Optional[int], constraint,
                deadline: Optional[float]) -> EnumResult:
    """`_drive`'s loop, resumable from mid-walk state — the entry point
    both for a fresh walk (`_drive` seeds the root) and for the
    device-resident deque's capacity-stall fallback (`_drive_resident`
    rebuilds ``work``/``stats``/outputs from the arena and continues
    here, so a stalled walk finishes with identical semantics)."""
    k = idx.k

    while work:
        if deadline is not None and clock.expired(deadline):
            return _finalize(idx, out_paths, out_lens, count, stats,
                             exhausted=False)
        paths, depth, cstate = work.pop()
        stats.chunks += 1
        expanded = step(paths, depth, cstate, stats, depth + 1 < k)
        if expanded is None:
            continue
        emit_rows, cont_rows, cont_state = expanded

        if emit_rows is not None and emit_rows.shape[0]:
            count += emit_rows.shape[0]
            stats.results += emit_rows.shape[0]
            if not count_only:
                out_paths.append(emit_rows)
                out_lens.append(np.full(emit_rows.shape[0], depth + 1,
                                        np.int32))
            if max_results is not None and count > max_results:
                raise EngineLimit(f"more than {max_results} results")
            if first_n is not None and count >= first_n:
                count = _trim_to_first_n(out_paths, out_lens, count,
                                         first_n, count_only, stats)
                return _finalize(idx, out_paths, out_lens, count, stats,
                                 exhausted=False)

        if cont_rows is not None and cont_rows.shape[0]:
            # split into chunks; push in reverse so earlier rows pop first
            pieces = range(0, cont_rows.shape[0], chunk_size)
            for st in reversed(list(pieces)):
                sl = slice(st, st + chunk_size)
                piece_cs = constraint.slice(cont_state, sl) \
                    if constraint is not None else None
                work.append((cont_rows[sl], depth + 1, piece_cs))

    return _finalize(idx, out_paths, out_lens, count, stats, exhausted=True,
                     canonical=True)


def _host_step(idx: LightweightIndex, constraint):
    """The numpy expansion step: `_expand_chunk` plus the Appendix-E
    constraint machinery (extend/accept/gather), folded to the driver's
    (emit_rows, cont_rows, cont_state) contract."""

    def step(paths, depth, cstate, stats, want_cont):
        expanded = _expand_chunk(idx, paths, depth, stats)
        if expanded is None:
            return None
        parent, pos, vnew, emit, cont = expanded

        if constraint is not None:
            eids = idx.fwd_eid[pos]
            cstate_new, keep = constraint.extend(cstate, parent, eids, vnew)
            pruned = (emit | cont) & ~keep
            stats.invalid_partials += int(pruned.sum())
            emit = emit & keep
            cont = cont & keep
        else:
            cstate_new = None

        def rows_of(sel):
            rows = paths[parent[sel]].copy()
            rows[:, depth + 1] = vnew[sel]
            return rows

        emit_rows = None
        if emit.any():
            sel = np.nonzero(emit)[0]
            if constraint is not None:
                acc = constraint.accept(cstate_new, sel)
                stats.invalid_partials += int((~acc).sum())
                sel = sel[acc]
            if sel.size:
                emit_rows = rows_of(sel)

        cont_rows, cont_state = None, None
        if want_cont and cont.any():
            sel = np.nonzero(cont)[0]
            cont_rows = rows_of(sel)
            cont_state = constraint.gather(cstate_new, sel) \
                if constraint is not None else None
        return emit_rows, cont_rows, cont_state

    return step


# Per-kernel-launch candidate-slot budget: a chunk whose (rows × padded
# fan-out) rectangle exceeds it is cut into contiguous row segments, so
# one hub vertex in a wide chunk cannot inflate the dense slot matrices
# past memory (the host path's work is proportional to actual candidates;
# the device rectangle is rows × max fan-out).  Segment outputs
# concatenate in row order, so emission order — and therefore every
# first_n prefix — is unchanged.
DEVICE_SLOT_BUDGET = 1 << 19


def _fanout_segments(cnt: np.ndarray, budget: int) -> List[Tuple[int, int]]:
    """Contiguous [start, end) row segments whose rows × next-pow2(max
    fan-out) rectangles each fit the slot budget (single rows always
    form a valid segment)."""
    # common case first, vectorized: the whole chunk's rectangle fits,
    # so the O(rows) scan below never runs on ordinary chunks
    whole = 1 << (max(int(cnt.max(initial=0)), 1) - 1).bit_length()
    if cnt.shape[0] * whole <= budget:
        return [(0, cnt.shape[0])]
    segments: List[Tuple[int, int]] = []
    start, seg_max = 0, 1
    for i in range(cnt.shape[0]):
        c = max(int(cnt[i]), 1)
        new_max = max(seg_max, 1 << (c - 1).bit_length())
        if i > start and (i - start + 1) * new_max > budget:
            segments.append((start, i))
            start, seg_max = i, 1 << (c - 1).bit_length()
        else:
            seg_max = new_max
    segments.append((start, cnt.shape[0]))
    return segments


def _device_step(idx: LightweightIndex):
    """The Pallas expansion step (DESIGN.md §9): one kernel launch per
    fan-out segment of the chunk, Fig.-6 counters accumulated from the
    kernel's device scalars.  The host keeps two cheap responsibilities:
    sizing segments off the offset arrays (which also shortcuts all-dead
    chunks without a launch), and the driver's usual splitting."""
    from ..kernels import ops as kops   # lazy: pallas only on this path
    k, t = idx.k, idx.t
    dev = idx.device_arrays()

    def step(paths, depth, cstate, stats, want_cont):
        last = paths[:, depth].astype(np.int64)
        b = k - depth - 1
        cnt = (idx.fwd_end[last, b] - idx.fwd_begin[last]) if b >= 0 \
            else np.zeros(paths.shape[0], np.int64)
        if int(cnt.sum()) == 0:
            stats.invalid_partials += paths.shape[0]
            return None
        emit_parts: List[np.ndarray] = []
        cont_parts: List[np.ndarray] = []
        for lo, hi in _fanout_segments(cnt, DEVICE_SLOT_BUDGET):
            emit_rows, cont_rows, n_emit, n_cont, counters = \
                kops.frontier_expand(paths[lo:hi], dev.begin, dev.end,
                                     dev.dst, depth=depth, t=t,
                                     max_deg=max(int(cnt[lo:hi].max()), 1),
                                     want_cont=want_cont)
            edges, partials, invalid, _ = (int(x) for x in
                                           np.asarray(counters))
            stats.edges_accessed += edges
            stats.partials_generated += partials
            stats.invalid_partials += invalid
            ne, nc = int(n_emit), int(n_cont)
            if ne:
                emit_parts.append(np.asarray(emit_rows[:ne]))
            if want_cont and nc:
                cont_parts.append(np.asarray(cont_rows[:nc]))
        # one array per chunk, like the host step: _trim_to_first_n
        # trims only the driver's last appended block
        emit_out = (np.concatenate(emit_parts, axis=0)
                    if emit_parts else None)
        cont_out = (np.concatenate(cont_parts, axis=0)
                    if cont_parts else None)
        return emit_out, cont_out, None

    return step


def _drive_resident(idx: LightweightIndex, chunk_size: int,
                    count_only: bool,
                    deadline: Optional[float]) -> EnumResult:
    """Device-resident deque driver (DESIGN.md §9, the tentpole of the
    device enumeration column): the LIFO chunk stack lives in a device
    arena and ``ops.frontier_deque_round`` runs many pop→expand→push
    iterations per host round-trip — the host syncs only to drain the
    round's emitted paths, fold its counters into ``EnumStats`` and
    check the cooperative ``deadline``.

    Semantics are `_drive` + `_device_step` bit-for-bit on every full
    enumeration: the in-arena push replicates the driver's chunk_size
    split and reversed piece order, so the pop sequence (and therefore
    ``stats.chunks`` and every Fig.-6 counter) is identical, and
    exhausted results pass through the same canonical sort.  Two
    escapes return to the host-looped driver: an index whose padded
    ``rows × fan-out`` rectangle exceeds the slot budget never enters
    (the host path segments wide chunks; the resident kernel cannot),
    and a capacity stall mid-walk (arena/emit/meta guard trips with
    chunks still queued) rebuilds the host work list from the arena and
    resumes `_drive_from` — same walk, same stats, different engine.
    ``REPRO_DEVICE_DEQUE=off|0`` disables the resident path entirely.
    """
    from ..kernels import ops as kops   # lazy: pallas only on this path
    k, s, t = idx.k, idx.s, idx.t
    max_deg = int((idx.fwd_end[:, k] - idx.fwd_begin).max(initial=0))
    cfg = kops.deque_config(k + 1, chunk_size, max_deg)
    if max_deg == 0 or cfg.cap > DEVICE_SLOT_BUDGET \
            or chunk_size > cfg.arena_cap:
        DRIVER_RUNS["device_loop"] += 1
        return _drive(idx, _device_step(idx), chunk_size=chunk_size,
                      count_only=count_only, first_n=None,
                      max_results=None, constraint=None, deadline=deadline)

    DRIVER_RUNS["resident"] += 1
    dev = idx.device_arrays()
    stats = EnumStats()
    out_paths: List[np.ndarray] = []
    out_lens: List[np.ndarray] = []
    count = 0
    root = np.full((k + 1,), PAD, dtype=np.int32)
    root[0] = s
    arena, m_depth, m_len, top, n_chunks = \
        kops.frontier_deque_init(root, cfg=cfg)

    while True:
        if deadline is not None and clock.expired(deadline):
            return _finalize(idx, out_paths, out_lens, count, stats,
                             exhausted=False)
        arena, m_depth, m_len, top, n_chunks, emitbuf, emitlen, n_emit, \
            counters, pops = kops.frontier_deque_round(
                arena, m_depth, m_len, top, n_chunks, dev.begin, dev.end,
                dev.dst, t, cfg=cfg)
        stats.chunks += int(pops)
        edges, partials, invalid, _ = (int(x) for x in np.asarray(counters))
        stats.edges_accessed += edges
        stats.partials_generated += partials
        stats.invalid_partials += invalid
        ne = int(n_emit)
        if ne:
            count += ne
            stats.results += ne
            if not count_only:
                out_paths.append(np.asarray(emitbuf[:ne]))
                out_lens.append(np.asarray(emitlen[:ne]))
        nc = int(n_chunks)
        if nc == 0:
            break
        if int(pops) == 0:
            # capacity stall: rebuild the host work list (meta slots
            # bottom→top; list.pop() then takes the top chunk first,
            # preserving the LIFO order) and finish on the host loop
            rows = np.asarray(arena[:int(top)])
            lens = np.asarray(m_len[:nc]).astype(np.int64)
            depths = np.asarray(m_depth[:nc])
            starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
            work: List[Tuple[np.ndarray, int, object]] = [
                (rows[starts[j]:starts[j] + lens[j]], int(depths[j]), None)
                for j in range(nc)]
            DRIVER_RUNS["resident_stall"] += 1
            return _drive_from(idx, _device_step(idx), work, stats,
                               out_paths, out_lens, count,
                               chunk_size=chunk_size,
                               count_only=count_only, first_n=None,
                               max_results=None, constraint=None,
                               deadline=deadline)

    return _finalize(idx, out_paths, out_lens, count, stats,
                     exhausted=True, canonical=True)


def _drive_ranked_heap(idx: LightweightIndex, spec: "rank.RankSpec",
                       chunk_size: int, count_only: bool,
                       first_n: Optional[int], max_results: Optional[int],
                       deadline: Optional[float]) -> EnumResult:
    """Best-first host driver for ranked enumeration (DESIGN.md §10).

    Two heaps over the canonical ``(cost, sequence)`` key:

      * *partials*, keyed by an admissible lower bound — accumulated
        cost so far plus ``rank.remaining_lower_bound`` at the frontier
        vertex (depth + dist_t for hops; the min-plus analogue for
        weights);
      * *results*, keyed by exact canonical cost.

    The emission gate: pop the minimum result only once it provably
    precedes every completion of every live partial — for hops an exact
    tuple compare against the minimum partial (the lexicographic
    extension property makes the tie case safe: a partial whose key ties
    the result extends to sequences that still compare after it), for
    weights a strict clearance of ``min bound − slack`` (see
    ``rank.WEIGHT_TIE_SLACK``; true ties then meet in the results heap,
    where canonical costs are bit-identical, and break exactly on the
    sequence).  Otherwise a batch of equal-depth partials is popped from
    the heap top and expanded through the same `_expand_chunk` hop the
    unranked driver uses — speculative expansion is always safe because
    emission order is decided solely by the gate.

    Anytime contracts: ``first_n`` stops after the n-th emission (the
    top-n); a deadline returns only the gated emissions — pending
    results cannot be flushed, an undiscovered path could still precede
    them — so the prefix is rank-optimal by construction.
    """
    k, s = idx.k, idx.s
    stats = EnumStats()
    out_paths: List[np.ndarray] = []
    out_lens: List[np.ndarray] = []
    count = 0
    lb = rank.remaining_lower_bound(idx, spec)
    zero = 0.0 if spec.is_weight else 0

    root = np.full(k + 1, PAD, dtype=np.int32)
    root[0] = s
    tick = 0  # heap tiebreak so comparison never reaches the ndarray
    # entry: (bound-or-cost, sequence tuple, tick, depth, row, acc)
    partials = [(zero + lb[s], (int(s),), tick, 0, root, zero)]
    results: List[Tuple] = []

    def gated(res_key, part_key):
        if spec.is_weight:
            return res_key[0] < part_key[0] - rank.weight_slack(part_key[0])
        return res_key[:2] < part_key[:2]

    while partials or results:
        if deadline is not None and clock.expired(deadline):
            return _finalize(idx, out_paths, out_lens, count, stats,
                             exhausted=False)
        if results and (not partials or gated(results[0], partials[0])):
            cost, _seq, _tick, depth, row, _acc = heapq.heappop(results)
            if first_n is not None and count >= first_n:
                return _finalize(idx, out_paths, out_lens, count, stats,
                                 exhausted=False)
            count += 1
            stats.results += 1
            if not count_only:
                out_paths.append(row[None, :])
                out_lens.append(np.full(1, depth, np.int32))
            if max_results is not None and count > max_results:
                raise EngineLimit(f"more than {max_results} results")
            if first_n is not None and count >= first_n:
                return _finalize(idx, out_paths, out_lens, count, stats,
                                 exhausted=False)
            continue

        batch = [heapq.heappop(partials)]
        depth = batch[0][3]
        while partials and len(batch) < chunk_size \
                and partials[0][3] == depth:
            batch.append(heapq.heappop(partials))
        rows = np.stack([e[4] for e in batch])
        accs = np.asarray([e[5] for e in batch])
        stats.chunks += 1
        expanded = _expand_chunk(idx, rows, depth, stats)
        if expanded is None:
            continue
        parent, pos, vnew, emit, cont = expanded
        acc_new = accs[parent] + rank.edge_step_costs(idx, spec, pos)

        for i in np.nonzero(emit)[0]:
            p = int(parent[i])
            row = rows[p].copy()
            row[depth + 1] = vnew[i]
            tick += 1
            heapq.heappush(results, (acc_new[i],
                                     batch[p][1] + (int(vnew[i]),),
                                     tick, depth + 1, row, acc_new[i]))
        if depth + 1 < k:
            for i in np.nonzero(cont)[0]:
                p = int(parent[i])
                row = rows[p].copy()
                row[depth + 1] = vnew[i]
                tick += 1
                heapq.heappush(partials,
                               (acc_new[i] + lb[vnew[i]],
                                batch[p][1] + (int(vnew[i]),),
                                tick, depth + 1, row, acc_new[i]))

    return _finalize(idx, out_paths, out_lens, count, stats, exhausted=True)


def _drive_ranked_buckets(idx: LightweightIndex, step, chunk_size: int,
                          count_only: bool, first_n: Optional[int],
                          max_results: Optional[int],
                          deadline: Optional[float]) -> EnumResult:
    """Rank-bucketed device driver for ``order="hops"`` (DESIGN.md §10).

    Hop bounds are integers, so the best-first frontier collapses into
    buckets: every partial row with lower bound ``b = depth + dist_t
    [last]`` lives in bucket ``b``.  Buckets drain in ascending order
    through the *unchanged* Pallas expansion step — a child either
    emits (cost exactly ``b``: an edge into t pins the parent's dist_t
    at 1) or re-buckets at ``depth+1 + dist_t[child] ≥ b`` (triangle
    inequality of BFS levels), so once bucket ``b`` is empty, its
    collected emissions are the complete cost-``b`` stratum.  One lex
    sort per stratum then yields the canonical ``(cost, sequence)``
    order, bit-identical to the host heap.

    Anytime contracts: ``first_n`` trims inside a sorted stratum; a
    deadline keeps only completed strata (the in-progress bucket's
    emissions are discarded — its stratum is incomplete, so any prefix
    through it could misorder) — again a rank-optimal prefix.
    """
    k, s = idx.k, idx.s
    stats = EnumStats()
    out_paths: List[np.ndarray] = []
    out_lens: List[np.ndarray] = []
    count = 0
    dist_t = idx.dist_t.astype(np.int64)

    root = np.full((1, k + 1), PAD, dtype=np.int32)
    root[0, 0] = s
    bucket_keys = [int(dist_t[s])]
    buckets = {int(dist_t[s]): [(root, 0)]}

    while bucket_keys:
        b = heapq.heappop(bucket_keys)
        pend = buckets.pop(b)
        stratum: List[np.ndarray] = []
        while pend:
            if deadline is not None and clock.expired(deadline):
                return _finalize(idx, out_paths, out_lens, count, stats,
                                 exhausted=False)
            rows, depth = pend.pop()
            stats.chunks += 1
            expanded = step(rows, depth, None, stats, depth + 1 < k)
            if expanded is None:
                continue
            emit_rows, cont_rows, _ = expanded
            if emit_rows is not None and emit_rows.shape[0]:
                stratum.append(emit_rows)
            if cont_rows is not None and cont_rows.shape[0] \
                    and depth + 1 < k:
                nb = depth + 1 + dist_t[cont_rows[:, depth + 1]]
                for val in np.unique(nb):
                    sel = cont_rows[nb == val]
                    if int(val) == b:
                        dest = pend
                    else:
                        dest = buckets.setdefault(int(val), [])
                        if len(dest) == 0:
                            heapq.heappush(bucket_keys, int(val))
                    for st in range(0, sel.shape[0], chunk_size):
                        dest.append((sel[st:st + chunk_size], depth + 1))
        if not stratum:
            continue
        allr = np.concatenate(stratum, axis=0)
        allr = allr[np.lexsort(tuple(allr[:, j] for j in range(k, -1, -1)))]
        nres = allr.shape[0]
        count += nres
        stats.results += nres
        if not count_only:
            out_paths.append(allr)
            out_lens.append(np.full(nres, b, np.int32))
        if max_results is not None and count > max_results:
            raise EngineLimit(f"more than {max_results} results")
        if first_n is not None and count >= first_n:
            count = _trim_to_first_n(out_paths, out_lens, count, first_n,
                                     count_only, stats)
            return _finalize(idx, out_paths, out_lens, count, stats,
                             exhausted=False)

    return _finalize(idx, out_paths, out_lens, count, stats, exhausted=True)


def _trim_to_first_n(out_paths, out_lens, count, first_n, count_only,
                     stats) -> int:
    """Drop the over-emitted tail of the last chunk so exactly ``first_n``
    results come back — the first-n counts then agree between the DFS and
    join paths regardless of either path's emission granularity.

    Which n rows survive is contract-dependent: under ``order`` the
    emitters feed this trim in canonical rank order, so the survivors
    are exactly the top-n; with ``order=None`` a truncated (non-
    exhausted) prefix stays *plan-defined* — DFS emission order for the
    dfs plans, key-group order for join — and only exhausted results are
    canonicalized (`_finalize(canonical=True)`)."""
    excess = count - first_n
    if excess > 0:
        stats.results -= excess
        if not count_only:
            out_paths[-1] = out_paths[-1][:-excess]
            out_lens[-1] = out_lens[-1][:-excess]
        count = first_n
    return count


def _finalize(idx, out_paths, out_lens, count, stats, exhausted,
              canonical: bool = False) -> EnumResult:
    """Concatenate emitted blocks into an EnumResult.  ``canonical``
    applies the hops-canonical ``(length, sequence)`` sort — requested
    only for *exhausted* unranked results, so every backend and plan
    returns the same ordered list on a full enumeration (ranked drivers
    already emit in their own canonical order, and truncated unranked
    prefixes stay plan-defined, see `_trim_to_first_n`)."""
    k = idx.k
    if out_paths:
        paths = np.concatenate(out_paths, axis=0)
        lens = np.concatenate(out_lens, axis=0)
        if canonical and paths.shape[0] > 1:
            perm = rank.canonical_perm(paths, lens.astype(np.int64))
            paths = paths[perm]
            lens = lens[perm]
    else:
        paths = np.zeros((0, k + 1), dtype=np.int32)
        lens = np.zeros((0,), dtype=np.int32)
    return EnumResult(paths=paths, lengths=lens, count=count, stats=stats,
                      exhausted=exhausted)
