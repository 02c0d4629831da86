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
        sds((M * N,)), sds((M * N,)), sds((M * MF,)), sds((M,), jnp.bool_),
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
