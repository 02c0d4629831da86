"""Frontier-expansion kernel unit tests (DESIGN.md §9).

The kernel's contract is *bit-for-bit* agreement with the host hot loop
``core/enumerate._expand_chunk``: same candidate set, same emit/continue
partition in the same order, and the same Fig.-6 counter deltas
(edges_accessed / partials_generated / invalid_partials).  On this CPU
container the kernel runs through the Pallas interpreter; on TPU the
same entry point compiles to Mosaic.

Layers: direct mask checks (PAD rows inert, prefix dedup vs a numpy
reference, emit/cont partition), counter parity against host EnumStats
over full enumerations, the backend contract regressions are in
test_engine.py / test_async_server.py (parametrized over backends), and
a hypothesis property drives random chunks through both expansions.
"""
import numpy as np
import pytest

from repro.core import build_index, erdos_renyi, from_edges, power_law
from repro.core.enumerate import (EnumStats, _expand_chunk,
                                  enumerate_paths_idx, resolve_backend)
from repro.core.graph import PAD
from repro.kernels import ops
from repro.kernels.frontier_expand import PAD as KERNEL_PAD

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _host_expand(idx, paths, depth):
    """Host `_expand_chunk` folded to (emit_rows, cont_rows, stats)."""
    stats = EnumStats()
    exp = _expand_chunk(idx, paths, depth, stats)
    empty = np.zeros((0, paths.shape[1]), np.int32)
    if exp is None:
        return empty, empty, stats
    parent, pos, vnew, emit, cont = exp

    def rows_of(mask):
        sel = np.nonzero(mask)[0]
        rows = paths[parent[sel]].copy()
        rows[:, depth + 1] = vnew[sel]
        return rows

    return rows_of(emit), rows_of(cont), stats


def _device_expand(idx, paths, depth):
    """Device expansion folded to the same (emit, cont, stats) triple.
    Returns None for zero-fanout chunks (the driver's host shortcut)."""
    last = paths[:, depth].astype(np.int64)
    b = idx.k - depth - 1
    cnt = idx.fwd_end[last, b] - idx.fwd_begin[last] if b >= 0 else 0 * last
    cnt = np.where(last >= 0, cnt, 0)
    if int(cnt.sum()) == 0:
        return None
    dev = idx.device_arrays()
    emit_rows, cont_rows, n_emit, n_cont, counters = ops.frontier_expand(
        paths, dev.begin, dev.end, dev.dst, depth=depth, t=idx.t,
        max_deg=int(cnt.max()))
    ne, nc = int(n_emit), int(n_cont)
    cs = np.asarray(counters)
    stats = EnumStats(edges_accessed=int(cs[0]), partials_generated=int(cs[1]),
                      invalid_partials=int(cs[2]), results=ne)
    return np.asarray(emit_rows[:ne]), np.asarray(cont_rows[:nc]), stats


def _chunk_at_depth(idx, depth):
    """A real chunk: the host frontier walked down to ``depth``."""
    paths = np.full((1, idx.k + 1), PAD, np.int32)
    paths[0, 0] = idx.s
    for d in range(depth):
        _, cont, _ = _host_expand(idx, paths, d)
        if cont.shape[0] == 0:
            return None
        paths = cont
    return paths


def test_pad_constant_matches_core():
    """The kernel's PAD sentinel is pinned to the core layout constant."""
    assert KERNEL_PAD == PAD == -1


def test_pad_rows_are_inert():
    """PAD padding rows contribute no candidates and no counters: the
    device output on a PAD-interleaved chunk equals the host output on
    the valid rows alone."""
    g = erdos_renyi(40, 4.0, seed=1)
    idx = build_index(g, 0, 7, 4)
    chunk = _chunk_at_depth(idx, 1)
    assert chunk is not None and chunk.shape[0] >= 2
    padded = np.full((chunk.shape[0] * 2, idx.k + 1), PAD, np.int32)
    padded[::2] = chunk                     # valid rows interleaved with PAD
    he, hc, hs = _host_expand(idx, chunk, 1)
    got = _device_expand(idx, padded, 1)
    assert got is not None
    de, dc, ds = got
    assert np.array_equal(de, he)
    assert np.array_equal(dc, hc)
    assert (ds.edges_accessed, ds.partials_generated, ds.invalid_partials) \
        == (hs.edges_accessed, hs.partials_generated, hs.invalid_partials)


def test_prefix_dedup_matches_numpy_reference():
    """The in-kernel simple-path check prunes exactly the candidates that
    appear in their row's prefix — checked against an explicit numpy
    recomputation on a cycle-heavy graph."""
    # hub-and-cycle digraph where depth-2 expansion revisits a prefix
    # vertex (found by search; the dup assertion below pins it)
    g = from_edges(8, np.array(
        [[0, 2], [0, 4], [0, 5], [0, 6], [1, 6], [2, 0], [2, 6], [3, 0],
         [3, 6], [4, 0], [4, 2], [4, 5], [5, 0], [5, 4], [5, 7], [6, 1],
         [6, 5], [7, 5], [4, 7]]))
    idx = build_index(g, 0, 2, 4)
    chunk = _chunk_at_depth(idx, 2)
    assert chunk is not None
    got = _device_expand(idx, chunk, 2)
    assert got is not None
    de, dc, ds = got
    # numpy reference: expand every row by its I_t list, drop prefix dups
    emit_ref, cont_ref, dup_n = [], [], 0
    for row in chunk:
        v = int(row[2])
        for vn in idx.it(v, idx.k - 3):
            if vn in row[:3]:
                dup_n += 1
            elif vn == idx.t:
                emit_ref.append(np.concatenate([row[:3], [vn], [PAD]]))
            else:
                cont_ref.append(np.concatenate([row[:3], [vn], [PAD]]))
    stack = lambda rs: (np.array(rs, np.int32) if rs
                        else np.zeros((0, 5), np.int32))
    assert np.array_equal(de, stack(emit_ref))
    assert np.array_equal(dc, stack(cont_ref))
    assert ds.invalid_partials >= dup_n       # dups plus dead rows
    assert dup_n > 0, "case must actually exercise the dedup"


def test_emit_cont_partition():
    """Every emitted row ends at t in column depth+1; no continue row
    does; emit + cont + pruned accounts for every generated partial."""
    g = power_law(80, 5.0, seed=4)
    idx = build_index(g, 0, 3, 4)
    chunk = _chunk_at_depth(idx, 1)
    assert chunk is not None
    got = _device_expand(idx, chunk, 1)
    assert got is not None
    de, dc, ds = got
    if de.size:
        assert (de[:, 2] == idx.t).all()
    if dc.size:
        assert (dc[:, 2] != idx.t).all()
    # partition: every generated partial is emitted, continued, or
    # dup-pruned (invalid_partials = dups + dead rows, so subtract dead)
    dups = ds.invalid_partials - _dead_rows(idx, chunk, 1)
    assert ds.partials_generated == de.shape[0] + dc.shape[0] + dups


def _dead_rows(idx, chunk, depth):
    """Rows of ``chunk`` none of whose expansions survive."""
    he, hc, _ = _host_expand(idx, chunk, depth)
    alive = set()
    for rows in (he, hc):
        for r in rows:
            alive.add(tuple(int(x) for x in r[: depth + 1]))
    return sum(1 for r in chunk
               if tuple(int(x) for x in r[: depth + 1]) not in alive)


@pytest.mark.parametrize("seed,s,t,k", [(0, 0, 7, 4), (1, 2, 9, 5),
                                        (2, 1, 5, 3)])
def test_counter_parity_with_host_enumstats(seed, s, t, k):
    """Full enumerations agree bit-for-bit across backends: paths,
    lengths, count, exhausted and every EnumStats field (including
    chunks — the chunk walk itself is shared)."""
    g = erdos_renyi(48, 4.0, seed=seed)
    idx = build_index(g, s, t, k)
    host = enumerate_paths_idx(idx)
    dev = enumerate_paths_idx(idx, backend="device")
    assert np.array_equal(host.paths, dev.paths)
    assert np.array_equal(host.lengths, dev.lengths)
    assert host.count == dev.count
    assert host.exhausted == dev.exhausted
    assert host.stats == dev.stats


def test_resolve_backend_fallback_matrix():
    """The §9 fallback matrix: host stays host; device always runs the
    kernel except for constrained queries; auto requires small k, a
    dense index and (on CPU) the CI force flag."""
    g = erdos_renyi(30, 3.0, seed=7)
    idx = build_index(g, 0, 5, 4)

    class _FakeConstraint:  # only identity matters to resolve_backend
        pass

    assert resolve_backend(idx, None) == "host"
    assert resolve_backend(idx, "host") == "host"
    assert resolve_backend(idx, "device") == "device"
    assert resolve_backend(idx, "device", _FakeConstraint()) == "host"
    assert resolve_backend(idx, "auto") == "host"  # sparse index and/or CPU
    with pytest.raises(ValueError):
        resolve_backend(idx, "gpu")
    with pytest.raises(ValueError):
        # a typo'd backend must raise even when the constraint fallback
        # would otherwise short-circuit to the host
        resolve_backend(idx, "devcie", _FakeConstraint())


def test_resolve_device_enum_env_matrix(monkeypatch):
    """The §9 escape-hatch row: REPRO_DEVICE_ENUM=off (or 0) is a uniform
    kill switch over every backend value — same spelling contract as
    REPRO_SHARING=off|0 / REPRO_PALLAS=off — while unrecognized values
    change nothing (only the documented force/off/0 spellings act)."""
    g = erdos_renyi(30, 3.0, seed=7)
    idx = build_index(g, 0, 5, 4)
    for off in ("off", "0", "OFF", "Off"):
        monkeypatch.setenv("REPRO_DEVICE_ENUM", off)
        for req in (None, "host", "device", "auto"):
            assert resolve_backend(idx, req) == "host", (off, req)
        # the kill switch silences even the CI force spelling wherever
        # both appear (off is the operator override, force the CI one)
        with pytest.raises(ValueError):
            resolve_backend(idx, "gpu")   # validation still runs first
    monkeypatch.setenv("REPRO_DEVICE_ENUM", "banana")  # unrecognized
    assert resolve_backend(idx, "device") == "device"
    monkeypatch.delenv("REPRO_DEVICE_ENUM")
    assert resolve_backend(idx, "device") == "device"
    # end-to-end: an explicit device request with the kill switch set
    # must produce the host path's results through the host expander
    monkeypatch.setenv("REPRO_DEVICE_ENUM", "off")
    res_off = enumerate_paths_idx(idx, backend="device")
    monkeypatch.delenv("REPRO_DEVICE_ENUM")
    res_host = enumerate_paths_idx(idx)
    assert res_off.as_tuples() == res_host.as_tuples()
    assert res_off.stats == res_host.stats


def test_auto_rule_forces_device_only_when_dense(monkeypatch):
    """REPRO_DEVICE_ENUM=force flips auto onto the device on CPU — but
    only for indexes dense enough to clear the threshold."""
    from repro.core import enumerate as en
    g = erdos_renyi(120, 20.0, seed=3)
    idx = build_index(g, 0, 9, 4)
    monkeypatch.setenv("REPRO_DEVICE_ENUM", "force")
    want = ("device" if idx.num_index_edges >= en.DEVICE_AUTO_MIN_EDGES
            else "host")
    assert resolve_backend(idx, "auto") == want
    monkeypatch.delenv("REPRO_DEVICE_ENUM")
    assert resolve_backend(idx, "auto") == "host"   # CPU, not forced


def test_resolve_sharing_env_matrix(monkeypatch):
    """The §13 escape hatch row of the fallback matrix: REPRO_SHARING=off
    (or 0) wins over every knob value, mirroring REPRO_DEVICE_ENUM."""
    from repro.core import sharing as sharing_mod
    assert sharing_mod.resolve_sharing("auto") == "auto"
    assert sharing_mod.resolve_sharing(None) == "auto"
    monkeypatch.setenv("REPRO_SHARING", "off")
    assert sharing_mod.resolve_sharing("auto") == "off"
    assert sharing_mod.resolve_sharing(None) == "off"
    monkeypatch.setenv("REPRO_SHARING", "0")
    assert sharing_mod.resolve_sharing("auto") == "off"
    monkeypatch.delenv("REPRO_SHARING")
    assert sharing_mod.resolve_sharing("auto") == "auto"


# ---------------------------------------------------------------------------
# random-chunk parity: host and device _expand_chunk agree bit-for-bit.
# Two layers: a deterministic seeded sweep that always runs (hypothesis
# is absent in some containers), and a shrinking hypothesis property.
# ---------------------------------------------------------------------------

def _random_chunk_case(seed):
    """(idx, paths, depth): a random index plus an arbitrary well-formed
    chunk at one depth — the expansion contract must hold for any chunk,
    reachable or not."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 30))
    m = max(1, int(n * float(rng.choice([1.0, 2.5, 4.0]))))
    g = from_edges(n, rng.integers(0, n, size=(m, 2)))
    s, t = map(int, rng.choice(n, 2, replace=False))
    k = int(rng.integers(2, 6))
    idx = build_index(g, s, t, k)
    depth = int(rng.integers(0, k - 1))
    rows = int(rng.integers(1, 18))
    paths = np.full((rows, k + 1), PAD, np.int32)
    paths[:, : depth + 1] = rng.integers(0, n, size=(rows, depth + 1))
    return idx, paths, depth


def _assert_host_device_chunk_parity(case):
    idx, paths, depth = case
    he, hc, hs = _host_expand(idx, paths, depth)
    got = _device_expand(idx, paths, depth)
    if got is None:       # zero fanout: host returned None too
        assert he.shape[0] == 0 and hc.shape[0] == 0
        assert hs.edges_accessed == 0
        return
    de, dc, ds = got
    assert np.array_equal(de, he)
    assert np.array_equal(dc, hc)
    assert (ds.edges_accessed, ds.partials_generated, ds.invalid_partials) \
        == (hs.edges_accessed, hs.partials_generated, hs.invalid_partials)


@pytest.mark.parametrize("seed", range(20))
def test_host_device_expand_bitwise_equal_seeded(seed):
    _assert_host_device_chunk_parity(_random_chunk_case(seed * 7919))


@pytest.mark.parametrize("rows,fanout", [(64, 3000), (128, 700)])
def test_wide_fanout_multiblock_parity(hub_graph, rows, fanout):
    """Fan-outs that split the mask grid: past COL_BLOCK the slots span
    several column blocks (dup/live counts summed across them), and wide
    fan-outs take row blocks under BLOCK_ROWS.  Rows, order and counters
    must still equal the host expansion; row prefixes hold leaves of the
    hub's own list, so dups fall in every column block."""
    from repro.kernels.frontier_expand import _blocks
    hubs = 4
    g = hub_graph(hubs, fanout, seed=rows)
    idx = build_index(g, 0, 1, 3)
    rng = np.random.default_rng(fanout)
    paths = np.full((rows, 4), PAD, np.int32)
    for r in range(rows):
        h = 2 + r % hubs
        own = idx.it(h, 1)
        paths[r, :2] = (int(rng.choice(own)) if r % 3 else 0, h)
    paths[-1, :2] = (0, g.n - 1)                 # dead row: z has no I_t
    max_deg = 1 << (int(idx.fwd_end[2, 1] - idx.fwd_begin[2]) - 1).bit_length()
    br, bc = _blocks(rows, max_deg)
    assert rows // br > 1
    assert (max_deg // bc > 1) == (fanout > 2048)
    _assert_host_device_chunk_parity((idx, paths, 1))


def test_fanout_segmentation_preserves_order_and_stats(monkeypatch):
    """A chunk cut into many fan-out segments (tiny DEVICE_SLOT_BUDGET)
    must produce the same paths, order and EnumStats as one launch — the
    memory guard may never change results."""
    from repro.core import enumerate as en
    g = erdos_renyi(48, 5.0, seed=6)
    idx = build_index(g, 0, 7, 4)
    host = enumerate_paths_idx(idx)
    monkeypatch.setattr(en, "DEVICE_SLOT_BUDGET", 4)
    dev = enumerate_paths_idx(idx, backend="device")
    assert np.array_equal(host.paths, dev.paths)
    assert host.stats == dev.stats


def test_fanout_segments_respect_budget_and_cover():
    """Segment rectangles fit the budget (except unavoidable single-row
    segments) and tile the chunk contiguously."""
    from repro.core.enumerate import _fanout_segments
    rng = np.random.default_rng(0)
    for _ in range(50):
        cnt = rng.integers(0, 40, size=int(rng.integers(1, 30)))
        budget = int(rng.choice([4, 16, 64]))
        segs = _fanout_segments(cnt, budget)
        assert segs[0][0] == 0 and segs[-1][1] == cnt.shape[0]
        for (a, b), (c, _) in zip(segs, segs[1:]):
            assert b == c
        for a, b in segs:
            assert b > a
            md = 1 << (max(int(cnt[a:b].max()), 1) - 1).bit_length()
            assert (b - a) * md <= budget or b - a == 1


if HAVE_HYPOTHESIS:

    @st.composite
    def random_chunk(draw):
        seed = draw(st.integers(0, 2**16))
        rng = np.random.default_rng(seed)
        n = int(rng.integers(5, 30))
        m = max(1, int(n * float(rng.choice([1.0, 2.5, 4.0]))))
        g = from_edges(n, rng.integers(0, n, size=(m, 2)))
        s, t = map(int, rng.choice(n, 2, replace=False))
        k = draw(st.integers(2, 5))
        idx = build_index(g, s, t, k)
        depth = draw(st.integers(0, k - 2))
        rows = draw(st.integers(1, 17))
        # arbitrary (not necessarily reachable) partials at this depth:
        # the expansion contract must hold for any well-formed chunk
        paths = np.full((rows, k + 1), PAD, np.int32)
        paths[:, : depth + 1] = rng.integers(0, n, size=(rows, depth + 1))
        return idx, paths, depth

    @settings(max_examples=25, deadline=None)
    @given(random_chunk())
    def test_hypothesis_host_device_expand_bitwise_equal(case):
        _assert_host_device_chunk_parity(case)
