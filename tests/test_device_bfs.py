"""The stacked bounded BFS on the device (core/bfs.py, DESIGN.md §4).

One jitted program computes the distances of a micro-batch's cache
misses over the graph's device copy.  These tests hold it to the host's
stacked relaxation (``batch.batched_bounded_bfs``) and to the queue BFS
of the oracle, row for row: excluded endpoints, rows whose k is below
the launch's, padded rows, vertices without predecessors, isolated
vertices and an empty edge set, and what the bit-packed rounds add: a
full 32-row mask and two masks, predecessor lists in buckets of width 1
to 64, rows that share an excluded vertex or a source.  The edges each
index keeps, listed on the device beside the distances, are the host
pass's.  Then the served path: with ``backend="device"`` the batch
engine's indexes are byte-identical to ``build_index``'s, its path sets
are the oracle's, and it fills no dense host offset table.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import BatchPathEnum, bfs, build_index, build_index_jax
from repro.core import from_edges, oracle
from repro.core.batch import batched_bounded_bfs
from repro.core.graph import random_graph_suite

SUITE = random_graph_suite(0)


def _queries(g, seed, ks):
    rng = np.random.default_rng(seed)
    out = []
    for k in ks:
        s, t = (int(v) for v in rng.choice(g.n, 2, replace=False))
        out.append((s, t, k))
    return out


def _check_rows(g, queries):
    """The device rows against the host stack and the oracle; padded
    rows stay at the launch's sentinel."""
    dist = np.asarray(bfs.stacked_bfs(g, queries))
    kmax = max(k for *_, k in queries)
    rows = 1 << max(len(queries) - 1, 0).bit_length()
    assert dist.shape == (2, rows, g.n) and dist.dtype == np.int8
    ss = np.array([q[0] for q in queries])
    tt = np.array([q[1] for q in queries])
    host_s = batched_bounded_bfs(g.rindptr, g.rindices, g.n, ss, tt, kmax)
    host_t = batched_bounded_bfs(g.indptr, g.indices, g.n, tt, ss, kmax)
    for i, (s, t, k) in enumerate(queries):
        want_s = oracle.bfs_dist_np(g, s, k, excluded=t)
        want_t = oracle.bfs_dist_np(g, t, k, reverse=True, excluded=s)
        np.testing.assert_array_equal(dist[0, i], want_s)
        np.testing.assert_array_equal(dist[1, i], want_t)
        np.testing.assert_array_equal(dist[0, i], np.minimum(host_s[i], k + 1))
        np.testing.assert_array_equal(dist[1, i], np.minimum(host_t[i], k + 1))
    assert (dist[:, len(queries):] == kmax + 1).all()


@pytest.mark.parametrize("name", sorted(SUITE))
def test_stacked_bfs_equals_host_and_oracle_row_for_row(name):
    # five rows padded to eight, k below the launch's kmax in three
    _check_rows(SUITE[name], _queries(SUITE[name], 3, [5, 2, 3, 5, 4]))


@pytest.mark.parametrize("name", sorted(SUITE))
def test_stacked_index_distances_equal_the_host_stack(name):
    from repro.core.batch import batched_index_distances
    g = SUITE[name]
    queries = _queries(g, 4, [4, 2, 6, 3, 4, 4, 2, 5, 3])
    got = bfs.stacked_index_distances(g, queries, block=4)
    want = batched_index_distances(g, queries, block=4)
    for (ds, dt), (ws, wt) in zip(got, want):
        assert ds.dtype == ws.dtype == np.int32
        np.testing.assert_array_equal(ds, ws)
        np.testing.assert_array_equal(dt, wt)


def _host_kept(g, s, t, k):
    """The ids of the edges ``build_index``'s own pass keeps, ascending."""
    return np.sort(build_index(g, s, t, k).fwd_eid)


@pytest.mark.parametrize("name", sorted(SUITE))
def test_kept_edges_equal_the_host_pass(name):
    # per-row k below the launch's, blocks of four with padded rows
    g = SUITE[name]
    queries = _queries(g, 6, [4, 2, 6, 3, 4, 5])
    got = bfs.stacked_index_inputs(g, queries, block=4)
    want = bfs.stacked_index_distances(g, queries, block=4)
    for (s, t, k), (ds, dt, kept), (ws, wt) in zip(queries, got, want):
        np.testing.assert_array_equal(ds, ws)
        np.testing.assert_array_equal(dt, wt)
        assert kept.dtype == np.int32
        np.testing.assert_array_equal(kept, _host_kept(g, s, t, k))


def test_kept_edges_past_the_cap_are_left_to_the_host(monkeypatch):
    g = SUITE["er_dense"]
    queries = _queries(g, 6, [4, 2, 6, 3, 4, 5])
    sizes = [_host_kept(g, *q).size for q in queries]
    cap = sorted(sizes)[1]
    assert min(sizes) <= cap < max(sizes)
    monkeypatch.setattr(bfs, "KEPT_CAP", cap)
    got = bfs.stacked_index_inputs(g, queries)
    for q, size, (_, _, kept) in zip(queries, sizes, got):
        if size > cap:
            assert kept is None
        else:
            np.testing.assert_array_equal(kept, _host_kept(g, *q))
    engine = BatchPathEnum(backend="device")
    engine.run(g, queries, count_only=True)
    for s, t, k in queries:
        _assert_same_index(engine.cache.get(("default", s, t, k, 0,
                                             g.version)),
                           build_index(g, s, t, k))


def test_kept_edges_of_an_empty_edge_set():
    g = from_edges(6, np.zeros((0, 2), np.int64))
    (_, _, kept), = bfs.stacked_index_inputs(g, [(1, 2, 3)])
    assert kept.size == 0


def _hub_graph():
    # hub 0 hears from 1..40 (a list of width 64) and speaks to 41..59;
    # in-degrees 1, 2, 3, 5 and 9 fill the buckets of width 1 to 16
    rng = np.random.default_rng(5)
    edges = [(u, 0) for u in range(1, 41)] + [(0, v) for v in range(41, 60)]
    for v, d in zip(range(41, 60), [1, 2, 3, 5, 9] * 4):
        edges += [(int(u), v) for u in rng.choice(range(1, 41), d,
                                                  replace=False)]
    edges += [(v, v - 40) for v in range(41, 60)]
    return from_edges(60, np.array(edges))


def _mask_case(name):
    """(graph, queries) that reach what the bit-packed rounds add."""
    g = SUITE["pl_hub"]
    if name in ("32_rows", "40_rows"):
        # 32 rows fill one 32-bit mask; 40 pad to 64 rows, two masks
        rows = int(name.split("_")[0])
        return g, _queries(g, rows, [2 + i % 4 for i in range(rows)])
    if name == "hub_buckets":
        h = _hub_graph()
        return h, [(41, 0, 4), (0, 50, 3), (45, 59, 5), (3, 7, 4), (0, 1, 2)]
    if name == "shared_excluded":
        return g, [(3, 60, 4), (11, 60, 5), (25, 60, 3)]
    if name == "shared_source":
        return g, [(7, 2, 4), (7, 70, 5), (7, 33, 3)]
    assert name == "no_in_edges"
    return from_edges(5, np.zeros((0, 2), np.int64)), [(0, 4, 3), (0, 2, 3),
                                                       (1, 4, 2)]


@pytest.mark.parametrize("name", ["32_rows", "40_rows", "hub_buckets",
                                  "shared_excluded", "shared_source",
                                  "no_in_edges"])
def test_mask_rounds_equal_host_and_oracle_row_for_row(name):
    _check_rows(*_mask_case(name))


def test_hub_graph_fills_the_pow2_buckets():
    widths = [w for w, _ in _hub_graph().device_arrays().fwd.buckets]
    assert widths == [1, 2, 4, 8, 16, 64]


def test_excluded_endpoint_relaxes_nothing_but_is_reached():
    # 0 -> 1 -> 2 -> 3 and 3 -> 1: from s = 0 with t = 1 excluded, t is
    # reached in one hop and nothing lies beyond it
    g = from_edges(4, np.array([[0, 1], [1, 2], [2, 3], [3, 1]]))
    dist = np.asarray(bfs.stacked_bfs(g, [(0, 1, 3)]))
    assert dist[0, 0].tolist() == [0, 1, 4, 4]
    # to t = 1 with s = 0 excluded: 3 -> 1, 2 -> 3, 1 -> 2 (t itself 0)
    assert dist[1, 0].tolist() == [1, 0, 2, 1]


def test_pred_free_and_isolated_vertices():
    # vertex 2 has no predecessor, 3 and 4 and the top ids are isolated
    g = from_edges(9, np.array([[0, 1], [2, 1], [1, 0], [2, 5], [5, 6]]))
    _check_rows(g, [(2, 0, 3), (0, 6, 4), (3, 4, 2)])


def test_empty_edge_set():
    g = from_edges(6, np.zeros((0, 2), np.int64))
    assert g.m == 0
    _check_rows(g, [(1, 2, 3), (0, 5, 2)])


def test_isolated_trailing_vertices_sweep():
    rng = np.random.default_rng(17)
    for _ in range(10):
        n = int(rng.integers(4, 20))
        m = int(rng.integers(1, 3 * n))
        g = from_edges(n, rng.integers(0, max(n - 2, 2), size=(m, 2)))
        _check_rows(g, _queries(g, int(rng.integers(1 << 30)), [2, 5, 3]))


def test_bounds_of_the_device_encoding_are_refused():
    g = SUITE["grid"]
    with pytest.raises(ValueError, match="overflows"):
        bfs.stacked_bfs(g, [(0, 1, 127)])


@pytest.mark.parametrize("backend", ["device", "auto"])
def test_budgets_past_the_device_encoding_take_the_host_bfs(backend,
                                                            monkeypatch):
    # a path 0 -> 1 -> ... -> 129: k = 127 needs int8 distances up to 128,
    # which the device BFS cannot hold, so the micro-batch's misses take
    # the host BFS and the query is served
    monkeypatch.setenv("REPRO_DEVICE_ENUM", "force")
    g = from_edges(130, np.stack([np.arange(129), np.arange(1, 130)], 1))
    assert not bfs.fits_device(g.n, 127) and bfs.fits_device(g.n, 126)
    engine = BatchPathEnum(backend=backend)
    out = engine.run(g, [(0, 120, 127), (3, 60, 4)], count_only=True)
    assert out.counts.tolist() == [1, 0]
    key = ("default", 0, 120, 127, 0, g.version)
    _assert_same_index(engine.cache.get(key), build_index(g, 0, 120, 127))


def _index_arrays(idx):
    return {f.name: getattr(idx, f.name) for f in dataclasses.fields(idx)}


def _assert_same_index(got, want):
    a, b = _index_arrays(got), _index_arrays(want)
    for name, v in b.items():
        if isinstance(v, np.ndarray):
            assert a[name].dtype == v.dtype, name
            assert a[name].tobytes() == v.tobytes(), name
        else:
            assert a[name] == v, name


@pytest.mark.parametrize("sharing", ["auto", "off"])
def test_device_engine_builds_the_host_indexes(sharing):
    g = SUITE["pl_hub"]
    queries = _queries(g, 9, [4, 3, 5, 4, 2, 4])
    engine = BatchPathEnum(backend="device", sharing=sharing)
    out = engine.run(g, queries, count_only=False)
    for (s, t, k), item in zip(queries, out.items):
        key = ("default", s, t, k, 0, g.version)
        _assert_same_index(engine.cache.get(key), build_index(g, s, t, k))
        want = oracle.paths_as_set(oracle.enumerate_paths(g, s, t, k))
        assert oracle.paths_as_set(item.result.as_tuples()) == want


def test_jit_build_runs_on_the_stacked_bfs():
    g = SUITE["er_small"]
    for s, t, k in _queries(g, 5, [2, 4, 5]):
        got, want = build_index_jax(g, s, t, k), build_index(g, s, t, k)
        np.testing.assert_array_equal(got.dist_s, want.dist_s)
        np.testing.assert_array_equal(got.dist_t, want.dist_t)
        np.testing.assert_array_equal(got.fwd_end, want.fwd_end)
        assert got.num_index_edges == want.num_index_edges


def test_served_indexes_fill_no_host_tables():
    # the fused device path reads an index's counts and device tables
    # from its sorted edges; the dense host tables wait for a reader
    g = SUITE["er_dense"]
    queries = _queries(g, 6, [4, 2, 6, 3, 4, 5])
    engine = BatchPathEnum(backend="device", fused="auto", sharing="off")
    out = engine.run(g, queries, count_only=True)
    assert out.fused_queries == len(queries)
    for s, t, k in queries:
        idx = engine.cache.get(("default", s, t, k, 0, g.version))
        for name in ("fwd_begin", "fwd_end", "rev_begin", "rev_end"):
            assert idx.__dict__["_" + name] is None, name
        want = build_index(g, s, t, k)
        dev = idx.device_arrays()
        np.testing.assert_array_equal(np.asarray(dev.begin),
                                      want.fwd_begin.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(dev.end),
                                      want.fwd_end.astype(np.int32))
        for b in range(-1, k + 2):
            np.testing.assert_array_equal(
                idx.it_count(np.arange(g.n), b),
                want.fwd_end[:, min(b, k)] - want.fwd_begin if b >= 0
                else np.zeros(g.n, np.int64))
        _assert_same_index(idx, want)
