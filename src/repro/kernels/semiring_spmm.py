"""Semiring SpMM Pallas kernels — the PathEnum device hot spot.

The paper's profile (Fig. 12a) shows index construction, dominated by the
two BFS passes, bounding response time on billion-edge graphs; the
full-fledged estimator adds k more edge sweeps (Alg. 5).  On TPU both are
k applications of a semiring matrix-vector product over the adjacency
matrix (DESIGN.md §2):

  * BFS relaxation  — (min, +):  dist' = min(dist, Aᵀ ⊕ dist)
  * walk-count DP   — (+, ×):    c'    = A ⊗ c          (Eq. 7)

Blocking: 128×128 adjacency tiles streamed HBM→VMEM.  min-plus has no MXU
form (the MXU is a multiply-accumulate array); it runs on the VPU over the
same tiling.  The counting semiring IS an MXU matmul: adjacency tiles are
{0,1} f32/bf16 masks and the DP vector a (n, q) block (q = batched queries),
so walk counting for a whole query batch is one tiled matmul per DP level.

Hardware-alignment contract (asserted): n multiple of BLOCK (wrappers in
ops.py pad), BLOCK multiple of 128 for MXU-native shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128


# ---------------------------------------------------------------------------
# min-plus SpMV:  out[v] = min(dist[v], min_u (adj[u, v] + dist[u]))
# ---------------------------------------------------------------------------

def _minplus_kernel(adj_ref, dist_in_ref, dist_keep_ref, out_ref, *, inf):
    i = pl.program_id(1)  # reduction block index (rows u)
    blk = adj_ref[...] + dist_in_ref[...]                  # (BI, BJ)
    part = jnp.min(blk, axis=0, keepdims=True)             # (1, BJ)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.minimum(dist_keep_ref[...], inf)

    out_ref[...] = jnp.minimum(out_ref[...], part)


@functools.partial(jax.jit, static_argnames=("inf", "interpret", "block"))
def minplus_spmv(adj: jnp.ndarray, dist: jnp.ndarray, *, inf: float,
                 interpret: bool = False, block: int = BLOCK) -> jnp.ndarray:
    """One bounded-BFS relaxation over a dense (n, n) adjacency.

    adj[u, v] = edge weight (1.0) or ``inf``; dist (n,) f32.  The
    distance vector enters as a (n, 1) column (the reduction operand,
    broadcast over a tile's columns) and as a (1, n) row (the kept
    distances and the output): 2-D blocks whose layout Mosaic accepts.
    """
    n = adj.shape[0]
    assert adj.shape == (n, n) and dist.shape == (n,)
    assert n % block == 0, f"pad n={n} to a multiple of {block} (ops.py does)"
    nb = n // block
    grid = (nb, nb)  # (j: output block, i: reduction block)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, inf=inf),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, block), lambda j, i: (i, j)),
            pl.BlockSpec((block, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((1, block), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), dist.dtype),
        interpret=interpret,
    )(adj, dist.reshape(n, 1), dist.reshape(1, n))
    return out.reshape(n)


# ---------------------------------------------------------------------------
# counting SpMM:  out = adj_mask @ counts      (plus-times, MXU path)
# ---------------------------------------------------------------------------

def _counting_kernel(adj_ref, cnt_ref, out_ref):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(adj_ref[...], cnt_ref[...],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def counting_spmm(adj_mask: jnp.ndarray, counts: jnp.ndarray, *,
                  interpret: bool = False, block: int = BLOCK) -> jnp.ndarray:
    """Walk-count DP level:  (n, n) {0,1} mask  @  (n, q) counts -> (n, q).

    q is the query-batch dimension — the engine runs the DP for a whole
    batch of concurrent queries in one MXU pass (beyond-paper batching,
    EXPERIMENTS.md §Perf).
    """
    n, q = counts.shape
    assert adj_mask.shape == (n, n)
    assert n % block == 0 and q % block == 0, "ops.py pads to block multiples"
    nm, nq, nk = n // block, q // block, n // block
    return pl.pallas_call(
        _counting_kernel,
        grid=(nm, nq, nk),
        in_specs=[
            pl.BlockSpec((block, block), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block, block), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, q), jnp.float32),
        interpret=interpret,
    )(adj_mask.astype(jnp.float32), counts.astype(jnp.float32))
