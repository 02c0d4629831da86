"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: kernel tests sweep shapes/dtypes and
assert allclose against these functions (tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Semiring SpMM (PathEnum BFS relaxation + walk-count DP)
# ---------------------------------------------------------------------------

def minplus_spmv_ref(adj: jnp.ndarray, dist: jnp.ndarray,
                     inf: float) -> jnp.ndarray:
    """One min-plus relaxation: out[v] = min(dist[v], min_u adj[u,v]+dist[u]).

    adj is a dense (n, n) matrix with 1.0 where an edge u->v exists and
    ``inf`` elsewhere (weights generalize to weighted graphs).
    """
    cand = jnp.min(adj + dist[:, None], axis=0)
    return jnp.minimum(dist, jnp.minimum(cand, inf))


def counting_spmv_ref(adj_mask: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """One plus-times pass of the walk DP: out[u] = Σ_v adj[u,v] * counts[v].

    adj_mask is (n, n) {0,1}; counts float32.  This is Eq. 7's inner sum.
    """
    return adj_mask.astype(counts.dtype) @ counts


def counting_spmm_ref(adj_mask: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """Batched walk DP: counts (n, q) — q independent queries at once."""
    return adj_mask.astype(counts.dtype) @ counts


# ---------------------------------------------------------------------------
# IDX-DFS frontier expansion (PathEnum Algorithm 4 hot loop)
# ---------------------------------------------------------------------------

def frontier_masks_ref(paths, begin, endb, dst, depth, t, max_deg: int,
                       pad: int = -1):
    """Pure-jnp oracle for kernels/frontier_expand.frontier_masks (the
    XLA gather stage plus ``_mask_kernel``).

    paths (C, k+1) int32 (PAD rows inert); begin/endb (n,) int32 offsets
    (endb pre-sliced to budget b = k - depth - 1); dst (mf,) int32;
    depth/t scalar int32.  Returns (vnew, emit, cont, counters) with the
    same shapes, masking and Fig.-6 counter semantics as frontier_masks.
    """
    C, k1 = paths.shape
    mf = dst.shape[0]
    last = jnp.take(paths, depth, axis=1)
    valid = last != pad
    lastc = jnp.where(valid, last, 0)
    bsel = jnp.take(begin, lastc)
    esel = jnp.take(endb, lastc)
    cnt = jnp.where(valid, esel - bsel, 0)
    slot = jnp.arange(max_deg, dtype=jnp.int32)[None, :]
    in_range = slot < cnt[:, None]
    pos = jnp.clip(bsel[:, None] + slot, 0, mf - 1)
    vnew = jnp.take(dst, pos)
    on_prefix = jnp.arange(k1, dtype=jnp.int32) <= depth        # (k1,)
    dup = ((paths[:, :, None] == vnew[:, None, :])
           & on_prefix[None, :, None]).any(axis=1)
    is_t = vnew == t
    emit = in_range & ~dup & is_t
    cont = in_range & ~dup & ~is_t
    alive = (emit | cont).any(axis=1)
    dead = valid & ~alive
    edges = jnp.sum(cnt)
    invalid = (jnp.sum((dup & in_range).astype(jnp.int32))
               + jnp.sum(dead.astype(jnp.int32)))
    counters = jnp.stack([edges, edges, invalid, jnp.int32(0)])
    return (jnp.where(emit | cont, vnew, pad), emit.astype(jnp.int32),
            cont.astype(jnp.int32), counters)


def frontier_fused_masks_ref(paths, rank, tvec, depthv, begin, endb, dst,
                             max_deg: int, pad: int = -1):
    """Pure-jnp oracle for kernels/frontier_expand.frontier_fused_masks
    (the XLA gather stage plus ``_mask_kernel``).

    paths (C, k1max) int32 rows packed member-rank-ascending (PAD rows
    inert); rank (C,) int32 member tags; tvec/depthv (m,) int32 per-member
    target/depth; begin/endb (m·n,) and dst (m·mfm,) int32 flattened
    per-member tables (endb pre-sliced to each member's budget column).
    Returns (vnew, emit, cont, counters) with counters (m, 4) per-member
    Fig.-6 rows — same semantics as frontier_fused_masks.
    """
    C, k1 = paths.shape
    m = tvec.shape[0]
    n = begin.shape[0] // m
    mfm = dst.shape[0] // m
    depth = jnp.take(depthv, rank)
    t = jnp.take(tvec, rank)
    last = jnp.take_along_axis(paths, depth[:, None], axis=1)[:, 0]
    valid = last != pad
    lastc = jnp.where(valid, last, 0)
    flat = rank * jnp.int32(n) + lastc
    bsel = jnp.take(begin, flat)
    esel = jnp.take(endb, flat)
    cnt = jnp.where(valid, esel - bsel, 0)
    slot = jnp.arange(max_deg, dtype=jnp.int32)[None, :]
    in_range = slot < cnt[:, None]
    pos = (jnp.clip(bsel[:, None] + slot, 0, mfm - 1)
           + rank[:, None] * jnp.int32(mfm))
    vnew = jnp.take(dst, pos)
    on_prefix = (jnp.arange(k1, dtype=jnp.int32)[None, :]
                 <= depth[:, None])                          # (C, k1)
    dup = ((paths[:, :, None] == vnew[:, None, :])
           & on_prefix[:, :, None]).any(axis=1)
    is_t = vnew == t[:, None]
    emit = in_range & ~dup & is_t
    cont = in_range & ~dup & ~is_t
    alive = (emit | cont).any(axis=1)
    dead = valid & ~alive
    edges_row = cnt
    invalid_row = (jnp.sum((dup & in_range).astype(jnp.int32), axis=1)
                   + dead.astype(jnp.int32))
    onehot = jnp.arange(m, dtype=jnp.int32)[None, :] == rank[:, None]
    edges_m = jnp.sum(jnp.where(onehot, edges_row[:, None], 0), axis=0)
    invalid_m = jnp.sum(jnp.where(onehot, invalid_row[:, None], 0), axis=0)
    counters = jnp.stack([edges_m, edges_m, invalid_m,
                          jnp.zeros_like(edges_m)], axis=1)
    return (jnp.where(emit | cont, vnew, pad), emit.astype(jnp.int32),
            cont.astype(jnp.int32), counters)


# ---------------------------------------------------------------------------
# Flash attention (LM prefill / train)
# ---------------------------------------------------------------------------

def mha_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            causal: bool = True, scale: float | None = None,
            window: int | None = None) -> jnp.ndarray:
    """Reference attention.  q (B, Lq, H, D), k/v (B, Lk, Hkv, D) with GQA
    broadcast when H != Hkv.  Optional causal mask and local window."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    group = H // Hkv
    kq = jnp.repeat(k, group, axis=2) if group > 1 else k
    vq = jnp.repeat(v, group, axis=2) if group > 1 else v
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(Lq)[:, None] + (Lk - Lq)
        ki = jnp.arange(Lk)[None, :]
        mask = qi >= ki
        if window is not None:
            mask &= (qi - ki) < window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vq.dtype), vq)
    return out.astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, lengths: jnp.ndarray,
                         scale: float | None = None) -> jnp.ndarray:
    """Single-token GQA decode.  q (B, H, D); caches (B, S, Hkv, D);
    lengths (B,) valid prefix lengths."""
    B, H, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    group = H // Hkv
    kq = jnp.repeat(k_cache, group, axis=2) if group > 1 else k_cache
    vq = jnp.repeat(v_cache, group, axis=2) if group > 1 else v_cache
    logits = jnp.einsum("bhd,bshd->bhs", q, kq).astype(jnp.float32) * scale
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(vq.dtype), vq)
    return out.astype(q.dtype)
