"""Fused multi-query device launch tests (DESIGN.md §9).

`core.fused.enumerate_fused_device` packs chunks from many queries into
single ``ops.frontier_expand_fused`` dispatches; `core.batch` routes a
batch's device-eligible dfs-plan queries through it.  The contract is
two-sided:

* **semantics**: every query's result is byte-identical to its solo
  ``enumerate_paths_idx(idx, backend="device")`` run — paths, count,
  ``EnumStats`` (chunk accounting included) and the ``first_n`` /
  ``count_only`` / deadline behaviors;
* **dispatch granularity** (the point of the tentpole): a micro-batch
  of N device-eligible queries issues ONE kernel dispatch per expansion
  round, not N — asserted here through ``ops.device_dispatch_count``
  deltas and ``BatchOutput.fused_dispatches``.
"""
import numpy as np
import pytest

from repro.core import build_index, clock, erdos_renyi, layered_dag
from repro.core.batch import BatchPathEnum
from repro.core.enumerate import enumerate_paths_idx
from repro.core.fused import enumerate_fused_device
from repro.kernels import ops as kops


def _assert_equal(a, b, tag=""):
    assert a.count == b.count, tag
    assert a.exhausted == b.exhausted, tag
    assert a.stats == b.stats, tag
    assert a.as_tuples() == b.as_tuples(), tag


def _graph_and_queries():
    g = erdos_renyi(40, 5.0, seed=17)
    qs = [(0, 39, 4), (1, 38, 4), (2, 37, 3), (3, 36, 4)]
    return g, qs


def _indexes(g, qs):
    out = []
    for s, t, k in qs:
        idx = build_index(g, s, t, k)
        if idx is not None:
            out.append(idx)
    return out


CHUNK = 7


def test_fused_bitwise_parity_with_solo_device(monkeypatch):
    monkeypatch.delenv("REPRO_DEVICE_DEQUE", raising=False)
    g, qs = _graph_and_queries()
    idxs = _indexes(g, qs)
    assert len(idxs) >= 2
    fused = enumerate_fused_device(idxs, chunk_size=CHUNK)
    # the solo oracle is the host-looped device driver (the deque takes
    # a different — but equivalent — chunk walk, so pin it off here)
    monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    for idx, fr in zip(idxs, fused):
        solo = enumerate_paths_idx(idx, backend="device", chunk_size=CHUNK)
        _assert_equal(fr, solo, f"s={idx.s} t={idx.t}")
        host = enumerate_paths_idx(idx, backend="host", chunk_size=CHUNK)
        _assert_equal(fr, host, f"s={idx.s} t={idx.t} vs host")


def test_fused_issues_one_dispatch_per_round_not_per_query(monkeypatch):
    monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    g, qs = _graph_and_queries()
    idxs = _indexes(g, qs)
    assert len(idxs) >= 2
    solo_chunks = 0
    solo_dispatches = 0
    for idx in idxs:
        before = kops.device_dispatch_count()
        r = enumerate_paths_idx(idx, backend="device", chunk_size=CHUNK)
        solo_dispatches += kops.device_dispatch_count() - before
        solo_chunks += r.stats.chunks
    before = kops.device_dispatch_count()
    enumerate_fused_device(idxs, chunk_size=CHUNK)
    fused_dispatches = kops.device_dispatch_count() - before
    # N queries × per-query chunk walks collapse into per-round launches
    assert 1 <= fused_dispatches < solo_dispatches
    assert fused_dispatches < solo_chunks


def test_fused_mixed_k_parity_with_solo_device(monkeypatch):
    """k = 3 and k = 4 members, one that never launches (no fan-out),
    finishing in different rounds of one run: every member keeps its
    slot of the once-built tables (six members, eight slots; the k = 3
    members' offset rows padded to k1max = 5), and each result equals
    its solo device run."""
    from repro import trace
    monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    g = erdos_renyi(40, 5.0, seed=17)
    qs = [(0, 39, 4), (1, 38, 3), (2, 37, 3), (3, 36, 4), (5, 34, 3),
          (6, 33, 4)]
    idxs = _indexes(g, qs)
    assert [ix.k for ix in idxs] == [k for *_, k in qs]
    before = trace.snapshot()
    fused = enumerate_fused_device(idxs, chunk_size=CHUNK)
    d = trace.delta(trace.snapshot(), before)["counters"]
    solos = [enumerate_paths_idx(idx, backend="device", chunk_size=CHUNK)
             for idx in idxs]
    for idx, fr, solo in zip(idxs, fused, solos):
        _assert_equal(fr, solo, f"s={idx.s} t={idx.t} k={idx.k}")
    chunks = [r.stats.chunks for r in solos]
    assert len(set(chunks)) >= 3 and min(chunks) == 1
    assert d["pathenum.enum.table_builds"] == 1
    assert d["pathenum.enum.rounds"] == max(chunks)


def test_fused_tables_stack_members_at_their_slots():
    """`ops.fused_tables`: member i at slot i, offset rows transposed to
    (k1max, n) with zero rows past a member's own k+1, slabs PAD-padded
    to the largest, and zero/PAD slots past the last member."""
    g = erdos_renyi(40, 5.0, seed=17)
    idxs = _indexes(g, [(0, 39, 4), (1, 38, 3), (2, 37, 3)])
    devs = [ix.device_arrays() for ix in idxs]
    begin, end_all, dst = kops.fused_tables(
        [d.begin for d in devs], [d.end for d in devs],
        [d.dst for d in devs], slots=4)
    n, mfm = g.n, max(d.dst.shape[0] for d in devs)
    assert begin.shape == (4 * n,) and end_all.shape == (4, 5, n)
    assert dst.shape == (4 * mfm,)
    begin, end_all, dst = (np.asarray(a) for a in (begin, end_all, dst))
    for slot, (ix, dv) in enumerate(zip(idxs, devs)):
        np.testing.assert_array_equal(begin[slot * n:(slot + 1) * n],
                                      ix.fwd_begin)
        np.testing.assert_array_equal(end_all[slot, :ix.k + 1].T,
                                      ix.fwd_end)
        assert not end_all[slot, ix.k + 1:].any()
        slab = dst[slot * mfm:(slot + 1) * mfm]
        mf = ix.fwd_dst.shape[0]
        np.testing.assert_array_equal(slab[:mf], ix.fwd_dst)
        assert (slab[mf:] == -1).all()
    assert not begin[3 * n:].any() and not end_all[3].any()
    assert (dst[3 * mfm:] == -1).all()


def test_fused_count_only_and_first_n(monkeypatch):
    monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    g, qs = _graph_and_queries()
    idxs = _indexes(g, qs)
    co = enumerate_fused_device(idxs, chunk_size=CHUNK, count_only=True)
    fn = enumerate_fused_device(idxs, chunk_size=CHUNK, first_n=2)
    for idx, c, f in zip(idxs, co, fn):
        solo_co = enumerate_paths_idx(idx, backend="device",
                                      chunk_size=CHUNK, count_only=True)
        assert c.count == solo_co.count and c.stats == solo_co.stats
        assert c.paths.shape[0] == 0
        solo_fn = enumerate_paths_idx(idx, backend="device",
                                      chunk_size=CHUNK, first_n=2)
        _assert_equal(f, solo_fn, "first_n")


def test_fused_deadline_expired_returns_nonexhausted():
    g, qs = _graph_and_queries()
    idxs = _indexes(g, qs)
    res = enumerate_fused_device(idxs, deadline=clock.now() - 1.0)
    for r in res:
        assert not r.exhausted
        assert r.count == 0


def test_fused_rejects_mixed_graphs():
    g1 = erdos_renyi(20, 4.0, seed=1)
    g2 = erdos_renyi(30, 4.0, seed=2)
    i1 = _indexes(g1, [(0, 19, 3)])
    i2 = _indexes(g2, [(0, 29, 3)])
    if not i1 or not i2:
        pytest.skip("no index")
    with pytest.raises(ValueError):
        enumerate_fused_device(i1 + i2)


def test_fused_wide_fanout_parity_with_host(hub_graph):
    """Members whose hub fan-out (over 2,048) spans two column blocks of
    the mask grid, with the hubs' back edges to s pruned as prefix
    duplicates in the second block: results and EnumStats equal the
    host engine's."""
    g = hub_graph(2, 3000, seed=5)
    idxs = _indexes(g, [(0, 1, 4), (0, 1, 3)])
    assert len(idxs) == 2
    assert int(idxs[0].fwd_end[2, 2] - idxs[0].fwd_begin[2]) > 2048
    before = kops.device_dispatch_fanouts()
    fused = enumerate_fused_device(idxs)
    assert kops.device_dispatch_fanouts().get(4096, 0) > before.get(4096, 0)
    for idx, fr in zip(idxs, fused):
        _assert_equal(fr, enumerate_paths_idx(idx, backend="host"),
                      f"s={idx.s} t={idx.t}")


def test_fused_ref_oracle_leg(monkeypatch):
    """REPRO_PALLAS=off routes the fused dispatch through the pure-jnp
    oracle; results stay byte-identical."""
    monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    g, qs = _graph_and_queries()
    idxs = _indexes(g, qs)
    fused = enumerate_fused_device(idxs, chunk_size=CHUNK)
    monkeypatch.setenv("REPRO_PALLAS", "off")
    fused_ref = enumerate_fused_device(idxs, chunk_size=CHUNK)
    for a, b in zip(fused, fused_ref):
        _assert_equal(a, b, "pallas vs ref")


# -- batch engine wiring ----------------------------------------------------

def test_batch_fused_parity_and_dispatch_count():
    g, qs = _graph_and_queries()
    host = BatchPathEnum(backend="host", fused="off", chunk_size=CHUNK)
    out_host = host.run(g, qs, count_only=True)
    fused = BatchPathEnum(backend="device", fused="auto", chunk_size=CHUNK)
    out_fused = fused.run(g, qs, count_only=True)
    for hi, fi in zip(out_host.items, out_fused.items):
        assert hi.result.count == fi.result.count, (hi.s, hi.t)
        assert hi.result.stats == fi.result.stats, (hi.s, hi.t)
    assert out_fused.fused_queries >= 2
    assert out_fused.fused_dispatches >= 1
    fused_items = [i for i in out_fused.items if i.fused]
    assert len(fused_items) >= 2
    # dispatch granularity: fewer launches than the members' summed
    # chunk walks (each round serves every member at once)
    total_chunks = sum(i.result.stats.chunks for i in fused_items)
    assert out_fused.fused_dispatches < total_chunks


def test_batch_fused_off_knob_pins_solo_path():
    g, qs = _graph_and_queries()
    off = BatchPathEnum(backend="device", fused="off", chunk_size=CHUNK)
    out = off.run(g, qs, count_only=True)
    assert out.fused_queries == 0
    assert out.fused_dispatches == 0
    assert not any(i.fused for i in out.items)
    on = BatchPathEnum(backend="device", fused="auto", chunk_size=CHUNK)
    out_on = on.run(g, qs, count_only=True)
    for a, b in zip(out.items, out_on.items):
        assert a.result.count == b.result.count
        assert a.result.stats == b.result.stats


def test_batch_fused_mixed_plans_auto_mode():
    """auto-mode batches with a mix of dfs and join plans fuse only the
    dfs-plan queries; join-plan queries run their normal pipeline."""
    g = erdos_renyi(120, 12.0, seed=21)
    qs = [(0, 119, 4), (1, 118, 4), (2, 117, 4), (3, 116, 4)]
    host = BatchPathEnum(backend="host", fused="off")
    out_host = host.run(g, qs, count_only=True, mode="auto")
    dev = BatchPathEnum(backend="device", fused="auto")
    out_dev = dev.run(g, qs, count_only=True, mode="auto")
    for hi, fi in zip(out_host.items, out_dev.items):
        assert hi.result.count == fi.result.count, (hi.s, hi.t)
        assert hi.result.stats == fi.result.stats, (hi.s, hi.t)
        assert hi.plan.method == fi.plan.method
    for item in out_dev.items:
        if item.fused:
            assert item.plan.method == "dfs"


def test_batch_fused_ranked_batches_never_fuse():
    g, qs = _graph_and_queries()
    dev = BatchPathEnum(backend="device", fused="auto")
    out = dev.run(g, qs, count_only=False, order="hops", first_n=3)
    assert out.fused_queries == 0
    assert not any(i.fused for i in out.items)


def test_batch_single_query_skips_fusion():
    g, qs = _graph_and_queries()
    dev = BatchPathEnum(backend="device", fused="auto")
    out = dev.run(g, qs[:1], count_only=True)
    assert out.fused_queries == 0


@pytest.mark.parametrize("m,n,mfm,C,max_deg", [
    (4, 16, 32, 16, 8),
    # fan-out past COL_BLOCK: two column blocks, 32-row blocks
    (2, 64, 8192, 64, 4096),
    # wide fan-out inside one column block: 64-row blocks
    (3, 64, 2048, 128, 1024),
])
def test_kernel_fused_matches_ref_oracle(m, n, mfm, C, max_deg):
    """Direct kernel-vs-oracle check on a packed multi-member chunk, at
    grid shapes from one block to several row and column blocks."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.frontier_expand import frontier_fused_masks

    rng = np.random.default_rng(3)
    k1 = 4
    paths = rng.integers(-1, n, (C, k1)).astype(np.int32)
    rank = np.sort(rng.integers(0, m, C)).astype(np.int32)
    tvec = rng.integers(0, n, m).astype(np.int32)
    depthv = rng.integers(0, k1 - 1, m).astype(np.int32)
    begin = rng.integers(0, mfm, m * n).astype(np.int32)
    endb = (begin + rng.integers(0, max_deg, m * n)).astype(np.int32)
    dst = rng.integers(0, n, m * mfm).astype(np.int32)
    args = tuple(jnp.asarray(a) for a in
                 (paths, rank, tvec, depthv, begin, endb, dst))
    got = frontier_fused_masks(*args, max_deg=max_deg, interpret=True)
    want = ref.frontier_fused_masks_ref(*args, max_deg=max_deg)
    for gv, wv, name in zip(got, want, ("vnew", "emit", "cont", "ctr")):
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv), name)
