"""Pallas TPU kernels for the perf-critical hot spots (DESIGN.md §2, §9).

semiring_spmm   — PathEnum BFS relaxation (min-plus) + walk-count DP (+,×)
frontier_expand — IDX-DFS frontier expansion (Algorithm 4's hot loop)

Validated on CPU via interpret=True against the pure-jnp oracles in ref.py.
"""
from . import ops, ref
from .ops import bfs_dense, counting_spmm, frontier_expand, minplus_spmv
