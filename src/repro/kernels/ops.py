"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: shape/alignment padding (kernels demand block multiples),
dtype plumbing, the XLA stages around each kernel (the frontier gathers
and the emit/continue compaction), and the interpret switch — when JAX's
backend is the CPU, kernels run with ``interpret=True`` (the Pallas
interpreter); otherwise Mosaic compiles them (tests/test_tpu_compile.py
compiles every frontier and semiring entry point for a TPU v5e).  Set
REPRO_PALLAS=off to route every op to its pure-jnp reference instead
(used to A/B the kernels inside the full system).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .frontier_expand import PAD, frontier_masks as _frontier_pallas
from .frontier_expand import frontier_fused_masks as _frontier_fused_pallas
from .semiring_spmm import BLOCK, counting_spmm as _counting_pallas
from .semiring_spmm import minplus_spmv as _minplus_pallas

# Monotone counts of frontier-expansion device dispatches (single-query,
# fused and deque-round launches alike), keyed by the launch's pow2
# fan-out bucket (the kernels' ``max_deg``).  The fused-launch and deque
# tests assert on deltas of the total — it is the ground truth for "one
# dispatch per expansion round" (DESIGN.md §9).
_dispatches: dict[int, int] = {}


def device_dispatch_count() -> int:
    """Total frontier-expansion kernel dispatches since process start."""
    return sum(_dispatches.values())


def device_dispatch_fanouts() -> dict[int, int]:
    """Dispatches since process start per pow2 fan-out bucket (a copy)."""
    return dict(_dispatches)


def _count_dispatch(max_deg: int) -> None:
    _dispatches[max_deg] = _dispatches.get(max_deg, 0) + 1


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _enabled() -> bool:
    return os.environ.get("REPRO_PALLAS", "on") != "off"


def _pad_to(x: jnp.ndarray, axis: int, mult: int,
            value: float) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# PathEnum semiring ops
# ---------------------------------------------------------------------------

def minplus_spmv(adj: jnp.ndarray, dist: jnp.ndarray, *, inf: float,
                 block: int = BLOCK) -> jnp.ndarray:
    """BFS relaxation step; pads n to the tile size."""
    if not _enabled():
        return ref.minplus_spmv_ref(adj, dist, inf)
    n = adj.shape[0]
    adj_p = _pad_to(_pad_to(adj, 0, block, inf), 1, block, inf)
    dist_p = _pad_to(dist, 0, block, inf)
    out = _minplus_pallas(adj_p, dist_p, inf=inf, interpret=_interpret(),
                          block=block)
    return out[:n]


def counting_spmm(adj_mask: jnp.ndarray, counts: jnp.ndarray, *,
                  block: int = BLOCK) -> jnp.ndarray:
    """Walk-count DP level for a query batch; pads (n, q) to tiles."""
    if not _enabled():
        return ref.counting_spmm_ref(adj_mask, counts)
    n, q = counts.shape
    adj_p = _pad_to(_pad_to(adj_mask, 0, block, 0), 1, block, 0)
    cnt_p = _pad_to(_pad_to(counts, 0, block, 0), 1, block, 0)
    out = _counting_pallas(adj_p, cnt_p, interpret=_interpret(), block=block)
    return out[:n, :q]


def bfs_dense(adj: jnp.ndarray, src: int | jnp.ndarray, k: int, *,
              inf: float = 1e9, block: int = BLOCK) -> jnp.ndarray:
    """Bounded BFS over a dense adjacency via k min-plus relaxations.

    This is the Pallas-kernel twin of core.bfs.bfs_edge_relax for the
    dense-tile regime (small/medium graphs, or per-partition tiles of the
    distributed engine).
    """
    n = adj.shape[0]
    dist = jnp.full((n,), inf, dtype=jnp.float32).at[src].set(0.0)

    def body(_: int, d: jnp.ndarray) -> jnp.ndarray:
        return minplus_spmv(adj, d, inf=inf, block=block)

    return jax.lax.fori_loop(0, k, body, dist)


# ---------------------------------------------------------------------------
# IDX-DFS frontier expansion (device-resident enumeration, DESIGN.md §9)
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length() if x > 1 else 1


# Row width of the blocked mask prefix sum below.
_SCAN_BLOCK = 64


def _mask_rank(mask: jnp.ndarray) -> jnp.ndarray:
    """Inclusive int32 prefix count of a 1-D bool mask (``jnp.cumsum``).

    Blocked: the mask, padded with False to 64-wide rows, goes through
    one {0,1} matmul against an upper-triangular ones matrix (exact:
    bf16 holds 0/1, the row sums stay below 2^24 in f32), and a flat
    prefix sum over the row totals adds the offsets.  A flat scan over
    the 2^19 candidate slots of one launch takes the TPU compiler
    several times longer than this form (PERF.md, Findings)."""
    L = mask.shape[0]
    padded = _pad_to(mask, 0, _SCAN_BLOCK, False)
    tri = jnp.triu(jnp.ones((_SCAN_BLOCK, _SCAN_BLOCK), jnp.bfloat16))
    inner = jnp.dot(padded.reshape(-1, _SCAN_BLOCK).astype(jnp.bfloat16),
                    tri, preferred_element_type=jnp.float32
                    ).astype(jnp.int32)
    tot = inner[:, -1]
    return (inner + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)[:L]


def _compact(mask: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Positions of the True entries of a 1-D mask, ascending, padded
    with 0 to the mask's length (``jnp.nonzero(size=, fill_value=0)``),
    plus their count."""
    L = mask.shape[0]
    rank = _mask_rank(mask)
    dest = jnp.where(mask, rank - 1, L)
    idxs = jnp.zeros((L,), jnp.int32).at[dest].set(
        jnp.arange(L, dtype=jnp.int32), mode="drop")
    return idxs, rank[-1]


def _children(paths: jnp.ndarray, vflat: jnp.ndarray, idxs: jnp.ndarray,
              depth: jnp.ndarray, max_deg: int) -> jnp.ndarray:
    """Materialize child rows for the compacted candidate indices: gather
    each candidate's parent row and write its vertex at column depth+1."""
    rows = jnp.take(paths, idxs // max_deg, axis=0)          # (cap, k1)
    col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return jnp.where(col == depth + 1, jnp.take(vflat, idxs)[:, None], rows)


@functools.partial(jax.jit,
                   static_argnames=("max_deg", "interpret", "use_ref",
                                    "want_cont"))
def _frontier_expand_jit(
        paths: jnp.ndarray, begin: jnp.ndarray, end: jnp.ndarray,
        dst: jnp.ndarray, meta: jnp.ndarray, *, max_deg: int,
        interpret: bool, use_ref: bool, want_cont: bool
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Masks (Pallas kernel or jnp ref) + compaction, one fused jit."""
    k1 = paths.shape[1]
    depth = meta[0]
    b = jnp.clip(k1 - 2 - depth, 0, k1 - 1)   # budget k - depth - 1
    endb = jnp.take(end, b, axis=1)
    if use_ref:
        vnew, emit, cont, counters = ref.frontier_masks_ref(
            paths, begin, endb, dst, depth, meta[1], max_deg, PAD)
    else:
        vnew, emit, cont, counters = _frontier_pallas(
            paths, begin, endb, dst, depth, meta[1], max_deg=max_deg,
            interpret=interpret)
    vflat = vnew.reshape(-1)
    eidx, n_emit = _compact(emit.reshape(-1) != 0)
    emit_rows = _children(paths, vflat, eidx, depth, max_deg)
    if want_cont:
        cidx, n_cont = _compact(cont.reshape(-1) != 0)
        cont_rows = _children(paths, vflat, cidx, depth, max_deg)
    else:
        # last hop: survivors can never extend, so skip the (cap, k+1)
        # gather the caller would discard (counters still see them)
        cont_rows = paths[:0]
        n_cont = jnp.int32(0)
    return emit_rows, cont_rows, n_emit, n_cont, counters


def frontier_expand(
        paths: np.ndarray | jnp.ndarray, fwd_begin: np.ndarray,
        fwd_end: np.ndarray, fwd_dst: np.ndarray, *, depth: int,
        t: int, max_deg: int, want_cont: bool = True
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """One IDX-DFS hop for a whole chunk, on device (DESIGN.md §9).

    paths is the (rows, k+1) int32 partial-path matrix at ``depth`` (PAD
    past the depth column); fwd_begin (n,) / fwd_end (n, k+1) / fwd_dst
    (mf,) are the int32 index arrays (``LightweightIndex.device_arrays``).
    ``max_deg`` is the chunk's max fan-out (callers read it off the host
    offset arrays; it must be ≥ 1 — zero-fanout chunks are the host
    driver's shortcut).

    Returns ``(emit_rows, cont_rows, n_emit, n_cont, counters)`` — all
    device-resident: the first ``n_emit`` rows of ``emit_rows`` are the
    completed paths (t written at depth+1) in exact host emission order,
    the first ``n_cont`` rows of ``cont_rows`` the surviving partials,
    and ``counters`` the (4,) int32 ``[edges_accessed,
    partials_generated, invalid_partials, 0]`` Fig.-6 scalars matching
    the host ``EnumStats`` deltas bit-for-bit.  ``want_cont=False``
    (the last hop, where survivors cannot extend) skips the continue
    compaction and returns an empty ``cont_rows`` with ``n_cont == 0``;
    counters are unaffected.

    Shapes are bucketed to powers of two (rows and fan-out) to bound jit
    recompiles; padded rows are PAD and inert.  ``REPRO_PALLAS=off``
    routes the mask stage to the pure-jnp reference.

    Ranked enumeration (DESIGN.md §10) reuses this kernel *unchanged*:
    the rank-bucketed driver (core/enumerate._drive_ranked_buckets)
    decides which chunks to expand and in what order — one hop-bound
    bucket at a time — but each launch is the same hop this docstring
    describes.  Rank awareness lives entirely in host scheduling.
    """
    paths = np.asarray(paths, dtype=np.int32)
    rows, k1 = paths.shape
    assert depth + 2 <= k1, f"depth {depth} leaves no column for the hop"
    assert max_deg >= 1, "zero-fanout chunks never reach the device"
    C = _next_pow2(max(rows, 8))
    if C != rows:
        paths = np.pad(paths, ((0, C - rows), (0, 0)), constant_values=PAD)
    meta = jnp.asarray([depth, t], jnp.int32)
    max_deg = _next_pow2(max_deg)
    _count_dispatch(max_deg)
    return _frontier_expand_jit(
        jnp.asarray(paths), jnp.asarray(fwd_begin), jnp.asarray(fwd_end),
        jnp.asarray(fwd_dst), meta, max_deg=max_deg,
        interpret=_interpret(), use_ref=not _enabled(),
        want_cont=want_cont)


def _children_fused(paths: jnp.ndarray, vflat: jnp.ndarray,
                    idxs: jnp.ndarray, depth_rows: jnp.ndarray,
                    max_deg: int) -> jnp.ndarray:
    """`_children` with a per-parent-row depth vector (fused launches mix
    members whose chunks sit at different depths)."""
    parents = idxs // max_deg
    rows = jnp.take(paths, parents, axis=0)                  # (cap, k1)
    col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    dsel = jnp.take(depth_rows, parents)
    return jnp.where(col == dsel[:, None] + 1,
                     jnp.take(vflat, idxs)[:, None], rows)


@functools.partial(jax.jit,
                   static_argnames=("max_deg", "interpret", "use_ref"))
def _frontier_fused_jit(
        paths: jnp.ndarray, rank: jnp.ndarray, tvec: jnp.ndarray,
        depthv: jnp.ndarray, begin: jnp.ndarray, endb: jnp.ndarray,
        dst: jnp.ndarray, wantc: jnp.ndarray, *, max_deg: int,
        interpret: bool, use_ref: bool
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Fused masks (Pallas kernel or jnp ref) + compaction, one jit.

    Compaction runs over the *flat* candidate order (row-major), and the
    wrapper packs rows member-rank-ascending, so the compacted emit and
    cont matrices are per-member contiguous segments in each member's
    exact solo emission order — the host slices them apart with the
    per-member counts.  Last-hop continue suppression happens HERE (the
    ``wantc`` per-member mask), after the kernel: the kernel always
    computes the full cont mask so dead-row and counter accounting
    matches the single-query kernel bit-for-bit.
    """
    m = tvec.shape[0]
    if use_ref:
        vnew, emit, cont, counters = ref.frontier_fused_masks_ref(
            paths, rank, tvec, depthv, begin, endb, dst, max_deg, PAD)
    else:
        vnew, emit, cont, counters = _frontier_fused_pallas(
            paths, rank, tvec, depthv, begin, endb, dst,
            max_deg=max_deg, interpret=interpret)
    vflat = vnew.reshape(-1)
    rankflat = jnp.repeat(rank, max_deg)
    depth_rows = jnp.take(depthv, rank)
    flat_emit = emit.reshape(-1) != 0
    eidx, _ = _compact(flat_emit)
    emit_rows = _children_fused(paths, vflat, eidx, depth_rows, max_deg)
    n_emit_m = jnp.zeros((m,), jnp.int32).at[rankflat].add(
        flat_emit.astype(jnp.int32))
    flat_cont = (cont.reshape(-1) != 0) & jnp.take(wantc, rankflat)
    cidx, _ = _compact(flat_cont)
    cont_rows = _children_fused(paths, vflat, cidx, depth_rows, max_deg)
    n_cont_m = jnp.zeros((m,), jnp.int32).at[rankflat].add(
        flat_cont.astype(jnp.int32))
    return emit_rows, cont_rows, n_emit_m, n_cont_m, counters


def frontier_expand_fused(
        paths: np.ndarray, rank: np.ndarray, tvec: np.ndarray,
        depthv: np.ndarray, begin: jnp.ndarray, endb: jnp.ndarray,
        dst: jnp.ndarray, wantc: np.ndarray, *, max_deg: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """One fused IDX-DFS hop for chunks of many queries (DESIGN.md §9).

    ``paths`` (rows, k1max) int32 packs one chunk per member, rows in
    ascending member order, each member's rows at its own common depth
    (columns past a member's own k+1 stay PAD); ``rank`` (rows,) int32
    tags each row's member; ``tvec``/``depthv`` (m,) int32 carry each
    member's target and chunk depth; ``begin``/``endb`` (m·n,) int32 are
    the flattened per-member offset tables (``endb`` pre-sliced to each
    member's budget column b = k − depth − 1); ``dst`` (m·mfm,) int32
    the flattened adjacency slabs (PAD-padded to the common ``mfm``);
    ``wantc`` (m,) bool is per-member ``want_cont`` (False on a member's
    last hop — suppression happens after the kernel so counters still
    see the candidates, exactly like the single-query path).

    Returns ``(emit_rows, cont_rows, n_emit_m, n_cont_m, counters)``:
    emit/cont row matrices in flat order (member-contiguous — slice
    member i's segment with the exclusive cumsum of ``n_emit_m`` /
    ``n_cont_m``), and ``counters`` the (m, 4) per-member Fig.-6 rows.
    All device-resident; one kernel dispatch per call.
    """
    paths = np.asarray(paths, dtype=np.int32)
    rows, _k1 = paths.shape
    assert max_deg >= 1, "zero-fanout chunks never reach the device"
    C = _next_pow2(max(rows, 8))
    if C != rows:
        paths = np.pad(paths, ((0, C - rows), (0, 0)), constant_values=PAD)
        rank = np.pad(np.asarray(rank, np.int32), (0, C - rows))
    max_deg = _next_pow2(max_deg)
    _count_dispatch(max_deg)
    return _frontier_fused_jit(
        jnp.asarray(paths), jnp.asarray(rank, dtype=jnp.int32),
        jnp.asarray(tvec, dtype=jnp.int32),
        jnp.asarray(depthv, dtype=jnp.int32), begin, endb, dst,
        jnp.asarray(wantc, dtype=bool), max_deg=max_deg,
        interpret=_interpret(), use_ref=not _enabled())


# ---------------------------------------------------------------------------
# Device-resident work deque (DESIGN.md §9): the IDX-DFS chunk stack
# lives in a device arena, and one jit'd while_loop pops/expands/pushes
# many chunks per host round-trip — the host syncs only to drain emitted
# paths and check the cooperative deadline.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DequeConfig:
    """Static geometry of the device-resident work deque.

    The arena is a row stack: live chunk rows occupy ``[0, top)`` and
    chunk ``j`` (meta slot ``j``, bottom to top) spans the rows between
    the cumulative lengths of its predecessors; pops read from the top,
    pushes scatter continue pieces back so the solo driver's reversed
    piece order is preserved (piece 0 topmost).  All capacities are
    static so one jit serves every round; the rows past ``arena_cap``
    (and the meta slots past ``max_chunks``) are scratch targets for
    masked scatters and are never read back.
    """
    k1: int              # path width k + 1
    chunk_size: int      # the driver's chunk split (cs)
    block_rows: int      # B: pow2 row height of one pop (>= chunk_size)
    max_deg: int         # pow2 fan-out bound of the whole index
    cap: int             # block_rows * max_deg candidate slots
    arena_cap: int       # live arena rows (stack region)
    arena_rows: int      # arena_cap + cap (scratch tail)
    emit_cap: int        # emitted rows one round may buffer
    max_chunks: int      # live meta slots
    max_pieces: int      # pow-bound on pieces one push can create
    round_pops: int      # pops per host round-trip


def deque_config(k1: int, chunk_size: int, max_deg: int,
                 round_pops: int = 64) -> DequeConfig:
    """Size a ``DequeConfig`` for one index/driver combination."""
    B = _next_pow2(max(chunk_size, 8))
    md = _next_pow2(max(max_deg, 1))
    cap = B * md
    arena_cap = max(8 * cap, 4 * B)
    emit_cap = max(4 * cap, 4 * B)
    maxp = cap // max(chunk_size, 1) + 2
    maxc = max(4096, 8 * maxp)
    return DequeConfig(k1=k1, chunk_size=chunk_size, block_rows=B,
                       max_deg=md, cap=cap, arena_cap=arena_cap,
                       arena_rows=arena_cap + cap, emit_cap=emit_cap,
                       max_chunks=maxc, max_pieces=maxp,
                       round_pops=round_pops)


def frontier_deque_init(root: np.ndarray, *, cfg: DequeConfig
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray, jnp.ndarray]:
    """Fresh deque state holding one root chunk (the (k+1,) root row)."""
    arena = jnp.full((cfg.arena_rows, cfg.k1), PAD, jnp.int32)
    arena = arena.at[0].set(jnp.asarray(root, jnp.int32))
    meta_depth = jnp.zeros((cfg.max_chunks + cfg.max_pieces,), jnp.int32)
    meta_len = meta_depth.at[0].set(1)
    return arena, meta_depth, meta_len, jnp.int32(1), jnp.int32(1)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret", "use_ref"))
def _deque_round_jit(
        arena: jnp.ndarray, meta_depth: jnp.ndarray, meta_len: jnp.ndarray,
        top: jnp.ndarray, n_chunks: jnp.ndarray, begin: jnp.ndarray,
        end: jnp.ndarray, dst: jnp.ndarray, t: jnp.ndarray, *,
        cfg: DequeConfig, interpret: bool, use_ref: bool
) -> tuple[jnp.ndarray, ...]:
    """One device round: a while_loop of in-arena pop → expand → push.

    Each iteration pops the top chunk, runs the mask stage (Pallas
    kernel or the jnp ref oracle), appends completed paths to the
    round's emit buffer, and scatters the surviving partials back into
    the arena as ``chunk_size`` pieces in the solo driver's reversed
    piece order — so the pop sequence, the chunk split and therefore
    every Fig.-6 counter are bit-identical to the host-looped device
    path.  The loop stops at ``round_pops``, an empty deque, or a
    conservative capacity guard (arena/emit/meta margin smaller than
    one worst-case push) — the host detects the zero-pop stall and
    rebuilds its own work list from the arena.
    """
    cs = cfg.chunk_size
    cap = cfg.cap
    B = cfg.block_rows
    k1 = cfg.k1

    def cond(state: tuple[jnp.ndarray, ...]) -> jnp.ndarray:
        _a, _md, _ml, s_top, s_nc, _eb, _el, s_ne, _c, s_pops = state
        return ((s_nc > 0) & (s_pops < cfg.round_pops)
                & (s_top + cap <= cfg.arena_cap)
                & (s_ne + cap <= cfg.emit_cap)
                & (s_nc + cfg.max_pieces <= cfg.max_chunks))

    def body(state: tuple[jnp.ndarray, ...]) -> tuple[jnp.ndarray, ...]:
        s_arena, s_md, s_ml, s_top, s_nc, s_eb, s_el, s_ne, s_ctr, \
            s_pops = state
        cidx = s_nc - 1
        clen = s_ml[cidx]
        cdepth = s_md[cidx]
        cstart = s_top - clen
        block = jax.lax.dynamic_slice(s_arena, (cstart, jnp.int32(0)),
                                      (B, k1))
        rowid = jnp.arange(B, dtype=jnp.int32)
        paths = jnp.where((rowid < clen)[:, None], block, PAD)
        s_top = cstart
        s_nc = cidx
        s_pops = s_pops + 1

        b = jnp.clip(k1 - 2 - cdepth, 0, k1 - 1)
        endb = jnp.take(end, b, axis=1)
        if use_ref:
            vnew, emit, cont, ctr1 = ref.frontier_masks_ref(
                paths, begin, endb, dst, cdepth, t, cfg.max_deg, PAD)
        else:
            vnew, emit, cont, ctr1 = _frontier_pallas(
                paths, begin, endb, dst, cdepth, t, max_deg=cfg.max_deg,
                interpret=interpret)
        s_ctr = s_ctr + ctr1
        vflat = vnew.reshape(-1)

        eidx, ne_new = _compact(emit.reshape(-1) != 0)
        echild = _children(paths, vflat, eidx, cdepth, cfg.max_deg)
        s_eb = jax.lax.dynamic_update_slice(s_eb, echild,
                                            (s_ne, jnp.int32(0)))
        s_el = jax.lax.dynamic_update_slice(
            s_el, jnp.full((cap,), cdepth + 1, jnp.int32), (s_ne,))
        s_ne = s_ne + ne_new

        # push: scatter cont children so piece 0 lands on top (the solo
        # driver pushes pieces reversed) with intra-piece order intact
        wantc = cdepth + 1 < jnp.int32(k1 - 1)
        flat_cont = (cont.reshape(-1) != 0) & wantc
        crank = _mask_rank(flat_cont) - 1
        n_cont = crank[-1] + 1
        piece = crank // cs
        np_pieces = (n_cont + cs - 1) // cs
        dest = (s_top + n_cont - jnp.minimum((piece + 1) * cs, n_cont)
                + (crank - piece * cs))
        dest = jnp.where(flat_cont, dest,
                         cfg.arena_cap + jnp.arange(cap, dtype=jnp.int32))
        children = _children(paths, vflat,
                             jnp.arange(cap, dtype=jnp.int32), cdepth,
                             cfg.max_deg)
        s_arena = s_arena.at[dest].set(children)
        pj = jnp.arange(cfg.max_pieces, dtype=jnp.int32)
        valid_p = pj < np_pieces
        slot = jnp.where(valid_p, s_nc + np_pieces - 1 - pj,
                         cfg.max_chunks + pj)
        s_md = s_md.at[slot].set(cdepth + 1)
        s_ml = s_ml.at[slot].set(jnp.clip(n_cont - pj * cs, 0, cs))
        s_top = s_top + n_cont
        s_nc = s_nc + np_pieces
        return (s_arena, s_md, s_ml, s_top, s_nc, s_eb, s_el, s_ne,
                s_ctr, s_pops)

    emitbuf = jnp.full((cfg.emit_cap + cap, k1), PAD, jnp.int32)
    emitlen = jnp.zeros((cfg.emit_cap + cap,), jnp.int32)
    state0 = (arena, meta_depth, meta_len, top, n_chunks, emitbuf,
              emitlen, jnp.int32(0), jnp.zeros((4,), jnp.int32),
              jnp.int32(0))
    return jax.lax.while_loop(cond, body, state0)


def frontier_deque_round(
        arena: jnp.ndarray, meta_depth: jnp.ndarray, meta_len: jnp.ndarray,
        top: jnp.ndarray, n_chunks: jnp.ndarray, begin: jnp.ndarray,
        end: jnp.ndarray, dst: jnp.ndarray, t: int, *, cfg: DequeConfig
) -> tuple[jnp.ndarray, ...]:
    """One host round-trip of the device-resident deque (DESIGN.md §9).

    Runs up to ``cfg.round_pops`` pop→expand→push iterations entirely on
    device and returns the updated deque state plus the round's outputs:
    ``(arena, meta_depth, meta_len, top, n_chunks, emitbuf, emitlen,
    n_emit, counters, pops)``.  The first ``n_emit`` rows of ``emitbuf``
    are the paths completed this round (``emitlen`` their hop counts);
    ``counters`` is the summed (4,) Fig.-6 vector and ``pops`` the
    number of chunks consumed (the driver's ``stats.chunks`` delta).  A
    round returning ``pops == 0`` with ``n_chunks > 0`` is a capacity
    stall: the caller rebuilds its host work list from ``arena[:top]``
    and the bottom ``n_chunks`` meta slots and resumes the host-looped
    driver.  ``REPRO_PALLAS=off`` routes the mask stage to the ref
    oracle; counted as one device dispatch per round.
    """
    _count_dispatch(cfg.max_deg)
    return _deque_round_jit(arena, meta_depth, meta_len, top, n_chunks,
                            begin, end, dst, jnp.asarray(t, jnp.int32),
                            cfg=cfg, interpret=_interpret(),
                            use_ref=not _enabled())


# ---------------------------------------------------------------------------
# LM attention ops
# ---------------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 128,
                    bk: int = 128) -> jnp.ndarray:
    if not _enabled():
        return ref.mha_ref(q, k, v, causal=causal, scale=scale, window=window)
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    bq_eff = min(bq, max(8, Lq))
    bk_eff = min(bk, max(8, Lk))
    needs_pad = (Lq % bq_eff != 0) or (Lk % bk_eff != 0)
    if needs_pad and (not causal or Lq != Lk):
        # Padding shifts the causal diagonal when Lq != Lk; production
        # shapes (4k/32k/500k) are tile-aligned so this fallback only
        # serves ragged test shapes.
        return ref.mha_ref(q, k, v, causal=causal, scale=scale, window=window)
    if needs_pad:
        # Lq == Lk: pad both ends equally.  Padded KV columns sit past every
        # real row index so the causal mask removes them; padded Q rows are
        # sliced off below.
        q = _pad_to(q, 1, bq_eff, 0)
        k = _pad_to(k, 1, bk_eff, 0)
        v = _pad_to(v, 1, bk_eff, 0)
        if q.shape[1] != k.shape[1]:
            pad_len = max(q.shape[1], k.shape[1])
            q = _pad_to(q, 1, pad_len, 0)
            k = _pad_to(k, 1, pad_len, 0)
            v = _pad_to(v, 1, pad_len, 0)
    out = _flash_pallas(q, k, v, causal=causal, window=window,
                        scale=scale, bq=bq_eff, bk=bk_eff,
                        interpret=_interpret())
    return out[:, :Lq]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray, *,
                     scale: float | None = None,
                     bs: int = 512) -> jnp.ndarray:
    if not _enabled():
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths,
                                        scale=scale)
    B, S, Hkv, D = k_cache.shape
    bs_eff = min(bs, max(8, S))
    k_p = _pad_to(k_cache, 1, bs_eff, 0)
    v_p = _pad_to(v_cache, 1, bs_eff, 0)
    return _decode_pallas(q, k_p, v_p, lengths, scale=scale, bs=bs_eff,
                          interpret=_interpret())
