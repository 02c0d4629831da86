"""Compile the device path for a described TPU v5e (no chip needed).

Interpret mode accepts kernels that Mosaic refuses (unaligned blocks,
in-kernel gathers, 1-D layouts), so every frontier and semiring entry
point is compiled here with ``interpret=False`` at deployment sizes: an
index over n = 2^20 vertices with 2^23 index edges and k + 1 = 7, at both
ends of the slot budget (128 rows × fan-out 4096, and 8192 rows × 64).
A stage that stays in Pallas must show up as a ``tpu_custom_call``.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler, and nothing happens at import time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.semiring_spmm import counting_spmm, minplus_spmv

N = 1 << 20           # vertices
MF = 1 << 23          # index edges (dst length)
K1 = 7                # k + 1
M = 8                 # fused members
HBM_BYTES = 16 * 10**9
SLOT_SHAPES = [(128, 4096), (8192, 64)]   # (rows, max fan-out)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def sds(one_chip, no_persistent_cache):
    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("rows,max_deg", SLOT_SHAPES)
def test_frontier_expand_compiles(sds, rows, max_deg):
    compiled = ops._frontier_expand_jit.lower(
        sds((rows, K1)), sds((N,)), sds((N, K1)), sds((MF,)), sds((2,)),
        max_deg=max_deg, interpret=False, use_ref=False,
        want_cont=True).compile()
    _check(compiled)


@pytest.mark.parametrize("rows,max_deg", SLOT_SHAPES)
def test_frontier_fused_compiles(sds, rows, max_deg):
    compiled = ops._frontier_fused_jit.lower(
        sds((rows, K1)), sds((rows,)), sds((M,)), sds((M,)),
        sds((M * N,)), sds((M, K1, N)), sds((M,)), sds((M * MF,)),
        sds((M,), jnp.bool_),
        max_deg=max_deg, interpret=False, use_ref=False).compile()
    _check(compiled)


@pytest.mark.parametrize("rows,max_deg", SLOT_SHAPES)
def test_deque_round_compiles(sds, rows, max_deg):
    cfg = ops.deque_config(K1, rows, max_deg)
    meta = cfg.max_chunks + cfg.max_pieces
    compiled = ops._deque_round_jit.lower(
        sds((cfg.arena_rows, K1)), sds((meta,)), sds((meta,)), sds(()),
        sds(()), sds((N,)), sds((N, K1)), sds((MF,)), sds(()),
        cfg=cfg, interpret=False, use_ref=False).compile()
    _check(compiled)


def test_minplus_spmv_compiles(sds):
    n = 2048
    compiled = minplus_spmv.lower(
        sds((n, n), jnp.float32), sds((n,), jnp.float32), inf=1e9,
        interpret=False).compile()
    _check(compiled)


def test_counting_spmm_compiles(sds):
    n, q = 2048, 128
    compiled = counting_spmm.lower(
        sds((n, n), jnp.float32), sds((n, q), jnp.float32),
        interpret=False).compile()
    _check(compiled)


# gg_pl's graph (benchmarks/hcpe/configs/gg_pl.json, seed 0): n, m and
# its (width, count) predecessor buckets, the same in both directions
# since every edge is symmetric; P = 6,764,387 padded list slots
GG_N, GG_M = 875_713, 5_113_754
GG_BUCKETS = ((1, 53_793), (2, 100_333), (4, 263_304), (8, 314_901),
              (16, 103_406), (32, 19_000), (64, 4_305), (128, 1_179),
              (256, 363), (512, 112), (1024, 38), (2048, 13), (4096, 4),
              (8192, 2))


def _gg_graph(sds):
    from repro.core.graph import DeviceGraph, PredLists
    slots = sum(w * c for w, c in GG_BUCKETS)
    assert slots == 6_764_387
    lists = PredLists(sds((slots,)), sds((GG_N,)), GG_BUCKETS)
    return DeviceGraph(sds((GG_M,)), sds((GG_M,)), lists, lists)


def test_bucket_layout_lists_every_edge_once():
    # the upload's layout, whose shapes the compiles below take, on a
    # small graph in both directions: widths the least power of two over
    # each list, vertices in bucket order, each list's edges once, and
    # padding only to the sentinel
    from repro.core.graph import power_law, pred_lists
    g = power_law(300, 5.0, seed=3)
    for ptr, idx in ((g.rindptr, g.rindices), (g.indptr, g.indices)):
        lists = pred_lists(ptr, idx)
        deg = np.diff(ptr)
        order = np.argsort(lists.pos)
        assert sorted(lists.pos.tolist()) == list(range(g.n))
        assert lists.pred.shape[0] == sum(w * c for w, c in lists.buckets)
        place, off = 0, 0
        for width, count in lists.buckets:
            block = lists.pred[off:off + width * count].reshape(width, count)
            members = order[place:place + count]
            assert (np.diff(members) > 0).all()
            for i, v in enumerate(members):
                assert width // 2 < deg[v] <= width
                got = block[:, i]
                np.testing.assert_array_equal(
                    np.sort(got[got != g.n]),
                    np.sort(lists.pos[idx[ptr[v]:ptr[v + 1]]]))
                assert (got == g.n).sum() == width - deg[v]
            place, off = place + count, off + width * count
        assert (deg[order[place:]] == 0).all()
        widths = [w for w, _ in lists.buckets]
        assert widths == sorted(set(widths))


@pytest.mark.parametrize("rows", [8, 40])
def test_stacked_bfs_compiles_at_web_google_size(sds, rows):
    # the gg_pl deployment, a burst of 8 misses at k = 4 (one 32-bit
    # mask), and 40 rows (two); no Pallas kernel, so only the memory is
    # checked
    from repro.core.bfs import _stacked_bfs_jit
    compiled = _stacked_bfs_jit.lower(
        _gg_graph(sds), sds((rows,)), sds((rows,)), sds((rows,)),
        kmax=4).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * rows * GG_N   # int8, tiled
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_kept_edges_compile_at_web_google_size(sds):
    # the edges each of a burst's 8 indexes keeps, listed beside the BFS
    from repro.core.bfs import KEPT_CAP, _kept_edges_jit
    rows = 8
    compiled = _kept_edges_jit.lower(
        _gg_graph(sds), sds((2, rows, GG_N), jnp.int8), sds((rows,)),
        sds((rows,)), sds((rows,)), cap=KEPT_CAP).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 4 * rows * (KEPT_CAP + 1)
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_device_offsets_compile_at_web_google_size(sds):
    # an index's int32 begin (n,) and end (n, k+1) from its sorted edges
    from repro.core.index import _device_offsets
    n, k, mf_pad = 875_713, 4, 4096
    compiled = _device_offsets.lower(sds((mf_pad,)), sds((mf_pad,)),
                                     n=n, k=k).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 4 * n * (k + 2)
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
