"""Device-resident IDX-DFS frontier expansion (DESIGN.md §9).

Algorithm 4's hot loop over fixed-width ``(chunk, k+1)`` int32 path
matrices, split in two stages so that every stage is legal on the TPU:

  * **gather** (XLA, in the jitted wrapper): each row's last vertex, its
    ``|I_t(v, k - depth - 1)|`` budget off the offset arrays
    (``fwd_begin`` / ``fwd_end``), and the dense ``(rows, max_deg)``
    candidate matrix read from ``fwd_dst`` at ``begin + slot``.  These
    are data-dependent gathers over arrays of graph size; XLA's TPU
    gather handles them, Mosaic does not.
  * **mask** (one Pallas kernel, ``_mask_kernel``): the simple-path
    prefix compare, the emit/continue partition and the per-row Fig.-6
    counter contributions, over tile-aligned ``(rows, max_deg)`` blocks.
    Per-row scalars (depth, target, candidate count) ride ``(rows, 1)``
    columns, so one kernel serves the single-query and the fused
    multi-query launches alike.

Layout contract (the padding/bucketing rules live in ops.frontier_expand):
  * path rows are partial s→v walks; columns past a row's depth hold PAD,
    and whole PAD rows (``paths[:, depth] == PAD``) are inert padding —
    no candidates, no counter contributions.
  * slot j of row r is real iff ``j < |I_t(v_r, k - depth_r - 1)|``.
  * outputs are the candidate-vertex matrix (PAD where pruned) plus
    emit/continue masks; compaction into dense row matrices happens in
    the jitted wrappers of ops.py.
  * counters are ``[edges_accessed, partials_generated,
    invalid_partials, 0]`` — bit-identical to the host ``EnumStats``
    deltas of core/enumerate._expand_chunk
    (tests/test_frontier_kernel.py asserts the parity).

On the CPU the kernel runs through the Pallas interpreter; on a TPU it is
compiled by Mosaic (tests/test_tpu_compile.py compiles it for a v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Must agree with core.graph.PAD; tests/test_frontier_kernel.py pins it.
PAD = -1

# Largest row block of the mask grid, and the slot tile: a block holds at
# most SLOT_TILE candidate slots, so wide fan-outs get fewer rows per
# block (and fan-outs past COL_BLOCK split over columns) and every block
# fits the TPU's scoped VMEM whatever the chunk's max fan-out.
BLOCK_ROWS = 128
COL_BLOCK = 2048
SLOT_TILE = 1 << 16


def _mask_kernel(paths_ref, depth_ref, t_ref, cnt_ref, vcand_ref,
                 vnew_ref, emit_ref, cont_ref, dups_ref, live_ref, *,
                 k1: int, bc: int, pad: int):
    """One (row-block, column-block) tile: prefix-dedup → partition."""
    j = pl.program_id(1)
    paths = paths_ref[...]                                  # (BR, k1)
    depth = depth_ref[...]                                  # (BR, 1)
    vcand = vcand_ref[...]                                  # (BR, BC)
    slot = (jax.lax.broadcasted_iota(jnp.int32, vcand.shape, 1)
            + j * bc)
    in_range = slot < cnt_ref[...]

    # simple-path check: v' must not appear in the row's depth+1 prefix
    # (unrolled over the static path width; columns past depth masked)
    dup = jnp.zeros(vcand.shape, jnp.bool_)
    for c in range(k1):
        dup = dup | ((depth >= c) & (paths[:, c:c + 1] == vcand))

    is_t = vcand == t_ref[...]
    emit = in_range & ~dup & is_t
    cont = in_range & ~dup & ~is_t
    live = emit | cont

    vnew_ref[...] = jnp.where(live, vcand, pad)
    emit_ref[...] = emit.astype(jnp.int32)
    cont_ref[...] = cont.astype(jnp.int32)

    # per-row Fig.-6 contributions, accumulated over the column blocks:
    # dup-pruned expansions, and live expansions (a valid row with none
    # is a dead row — folded into invalid_partials by `_dead_invalid`)
    @pl.when(j == 0)
    def _init():
        dups_ref[...] = jnp.zeros_like(dups_ref)
        live_ref[...] = jnp.zeros_like(live_ref)

    dups_ref[...] += jnp.sum((dup & in_range).astype(jnp.int32), axis=1,
                             keepdims=True)
    live_ref[...] += jnp.sum(live.astype(jnp.int32), axis=1, keepdims=True)


def _blocks(C: int, D: int) -> tuple[int, int]:
    """(row block, column block) of the mask grid for a (C, D) chunk."""
    bc = min(D, COL_BLOCK)
    br = min(C, BLOCK_ROWS, max(8, SLOT_TILE // bc))
    return br, bc


def _mask(paths, depth_rows, t_rows, cnt, vcand, *, interpret: bool):
    """Run ``_mask_kernel`` over a (C, D) candidate matrix.

    ``depth_rows``/``t_rows``/``cnt`` are (C, 1) int32 columns.  Returns
    (vnew, emit, cont, dups, live) with dups/live the per-row (C, 1)
    counter contributions.
    """
    C, k1 = paths.shape
    D = vcand.shape[1]
    br, bc = _blocks(C, D)
    assert C % br == 0 and D % bc == 0, \
        f"pad chunk (C={C}, max_deg={D}) to the ({br}, {bc}) tile"
    row = lambda i, j: (i, 0)
    tile = lambda i, j: (i, j)
    col_spec = pl.BlockSpec((br, 1), row)
    slots = jax.ShapeDtypeStruct((C, D), jnp.int32)
    per_row = jax.ShapeDtypeStruct((C, 1), jnp.int32)
    return pl.pallas_call(
        functools.partial(_mask_kernel, k1=k1, bc=bc, pad=PAD),
        grid=(C // br, D // bc),
        in_specs=[pl.BlockSpec((br, k1), row), col_spec, col_spec, col_spec,
                  pl.BlockSpec((br, bc), tile)],
        out_specs=[pl.BlockSpec((br, bc), tile)] * 3 + [col_spec] * 2,
        out_shape=[slots, slots, slots, per_row, per_row],
        interpret=interpret,
    )(paths, depth_rows, t_rows, cnt, vcand)


def _gather(paths, depth_rows, begin, endb, dst, row_base, dst_base,
            max_deg: int, mf: int):
    """The XLA gather stage: each row's last vertex, its candidate count
    and its dense (C, max_deg) candidate matrix.  ``row_base`` offsets a
    row's reads into flattened per-member offset tables and ``dst_base``
    into the flattened adjacency slabs (both zero for one query);
    candidate positions clip within a row's own ``mf``-long slab."""
    last = jnp.take_along_axis(paths, depth_rows, axis=1)   # (C, 1)
    valid = last != PAD
    flat = row_base + jnp.where(valid, last, 0)
    bsel = jnp.take(begin, flat[:, 0])[:, None]
    esel = jnp.take(endb, flat[:, 0])[:, None]
    cnt = jnp.where(valid, esel - bsel, 0)                  # |I_t(v, b)|
    slot = jax.lax.broadcasted_iota(jnp.int32, (paths.shape[0], max_deg), 1)
    pos = jnp.clip(bsel + slot, 0, mf - 1) + dst_base
    return valid, cnt, jnp.take(dst, pos)


def _dead_invalid(valid, dups, live):
    """Per-row invalid partials: dup-pruned expansions plus the row
    itself when it is valid and none of its expansions survived."""
    return dups + (valid & (live == 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_deg", "interpret"))
def frontier_fused_masks(paths, rank, tvec, depthv, begin, endb, dst, *,
                         max_deg: int, interpret: bool = False):
    """Raw fused entry: masks + per-member counters, no compaction.

    Packs chunks from ``m`` queries (an async micro-batch, or a merged
    sharing group's member views) into one row matrix tagged by member
    rank and expands them all in a single dispatch (DESIGN.md §9).
    paths (C, k1max) int32 rows packed member-rank-ascending (PAD rows
    inert, rank 0); rank (C,) int32; tvec/depthv (m,) int32 each member's
    target and chunk depth; begin/endb (m·n,) int32 each member's offset
    vectors (``endb`` pre-sliced to the member's budget column b =
    k−depth−1), row r reads at ``rank_r·n + last_r``; dst (m·mfm,) int32
    each member's adjacency slab padded to the common ``mfm`` —
    candidate positions clip within the member's slab, so no row can
    read a neighbor member's edges.

    Returns (vnew, emit, cont, counters) with counters (m, 4), one
    Fig.-6 row per member, so the host driver credits each query's
    ``EnumStats`` exactly as if it had run solo
    (tests/test_fused_launch.py pins the bit-parity).
    """
    m = tvec.shape[0]
    n = begin.shape[0] // m
    mfm = dst.shape[0] // m
    rank_c = rank[:, None]
    depth_rows = jnp.take(depthv, rank)[:, None]
    t_rows = jnp.take(tvec, rank)[:, None]
    valid, cnt, vcand = _gather(paths, depth_rows, begin, endb, dst,
                                rank_c * jnp.int32(n),
                                rank_c * jnp.int32(mfm), max_deg, mfm)
    vnew, emit, cont, dups, live = _mask(paths, depth_rows, t_rows, cnt,
                                         vcand, interpret=interpret)
    per_row = jnp.concatenate(
        [cnt, cnt, _dead_invalid(valid, dups, live), jnp.zeros_like(cnt)],
        axis=1)                                             # (C, 4)
    counters = jax.ops.segment_sum(per_row, rank, num_segments=m)
    return vnew, emit, cont, counters


def frontier_masks(paths, begin, endb, dst, depth, t, *, max_deg: int,
                   interpret: bool):
    """Single-query masks + counters, traced inside the callers' jits.

    paths (C, k+1) int32 at one common ``depth``; begin/endb (n,) int32
    offset vectors (endb already sliced to the budget column); dst (mf,)
    int32; depth/t int32 scalars.  Returns (vnew, emit, cont, counters)
    with counters the (4,) Fig.-6 vector.
    """
    C = paths.shape[0]
    depth_rows = jnp.full((C, 1), depth, jnp.int32)
    t_rows = jnp.full((C, 1), t, jnp.int32)
    zero = jnp.zeros((C, 1), jnp.int32)
    valid, cnt, vcand = _gather(paths, depth_rows, begin, endb, dst, zero,
                                zero, max_deg, dst.shape[0])
    vnew, emit, cont, dups, live = _mask(paths, depth_rows, t_rows, cnt,
                                         vcand, interpret=interpret)
    edges = jnp.sum(cnt)
    invalid = jnp.sum(_dead_invalid(valid, dups, live))
    counters = jnp.stack([edges, edges, invalid, jnp.int32(0)])
    return vnew, emit, cont, counters

