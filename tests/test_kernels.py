"""Pallas kernels: shape/dtype sweeps asserting allclose vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.semiring_spmm import counting_spmm as raw_counting
from repro.kernels.semiring_spmm import minplus_spmv as raw_minplus

RNG = np.random.default_rng(0)
INF = 1e9


# ---------------------------------------------------------------------------
# semiring kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 128, 200, 384])
def test_minplus_sweep(n):
    adj_m = (RNG.random((n, n)) < 0.05)
    adj = np.where(adj_m, 1.0, INF).astype(np.float32)
    dist = np.where(RNG.random(n) < 0.2, RNG.integers(0, 5, n), INF).astype(
        np.float32)
    got = ops.minplus_spmv(jnp.array(adj), jnp.array(dist), inf=INF)
    want = ref.minplus_spmv_ref(jnp.array(adj), jnp.array(dist), INF)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("n,q", [(128, 128), (256, 64), (200, 40), (64, 8)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_counting_sweep(n, q, dtype):
    adj = (RNG.random((n, n)) < 0.05).astype(np.float32)
    counts = RNG.integers(0, 8, size=(n, q)).astype(dtype)
    got = ops.counting_spmm(jnp.array(adj), jnp.array(counts, np.float32))
    want = ref.counting_spmm_ref(jnp.array(adj),
                                 jnp.array(counts, np.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_bfs_dense_matches_edge_relax():
    from repro.core import erdos_renyi
    from repro.core.bfs import index_distances
    g = erdos_renyi(150, 3.0, seed=2)
    A = np.full((g.n, g.n), INF, np.float32)
    A[g.esrc, g.edst] = 1.0
    for k in (2, 5):
        dd = np.asarray(ops.bfs_dense(jnp.array(A), 0, k, inf=INF))
        de, _ = index_distances(g, 0, -1, k)
        same = np.minimum(dd, k + 1) == np.minimum(de, k + 1)
        assert np.all(same | ((dd >= k + 1) & (de >= k + 1)))


def test_raw_kernels_require_alignment():
    with pytest.raises(AssertionError):
        raw_minplus(jnp.zeros((100, 100)), jnp.zeros((100,)), inf=INF,
                    interpret=True)
    with pytest.raises(AssertionError):
        raw_counting(jnp.zeros((100, 100)), jnp.zeros((100, 4)),
                     interpret=True)
