"""The span-and-counter tally of the served path (DESIGN.md §12).

`repro.trace` times the phases of a batch and of each fused round and
counts launches, slots, rounds and host↔device bytes.  These tests run
one fused device batch (CPU, interpret mode) under a profiler trace with
the fused launcher watched, and hold the tally to what the launches
did: dispatches, rounds, padded slots, bytes copied back, the misses'
stacked BFS launch and the graph's one upload, the ``BatchTiming``
fields the spans feed, and the phases' cover of the fused enumeration.
Then the solo device drivers, thread safety under ``AsyncHcPEServer``,
and the Prometheus export.
"""
import asyncio
import pathlib
import sys
import threading

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import erdos_renyi
from repro.core import bfs
from repro.core import enumerate as en
from repro.core.batch import BatchPathEnum
from repro.kernels import ops as kops
from repro.serving import AsyncHcPEServer, HcPEServer, PathQueryRequest
from repro.serving.metrics import snapshot

REPO = pathlib.Path(__file__).resolve().parents[1]
CHUNK = 7
PHASES = ("pop", "pack", "tables", "dispatch", "sync", "split", "tail")


def _pow2(x):
    return 1 << max(x - 1, 0).bit_length() if x > 1 else 1


def _graph_and_queries():
    g = erdos_renyi(40, 5.0, seed=17)
    return g, [(0, 39, 4), (1, 38, 4), (2, 37, 3), (3, 36, 4)]


def _spans(d, name):
    return d["spans"].get(name, [0.0, 0])


@pytest.fixture(scope="module")
def fused_batch(tmp_path_factory):
    """One fused device batch, traced, with every fused launch's inputs
    and outputs and every table build kept: (BatchOutput, tally delta,
    launches, trace dir, tables)."""
    g, qs = _graph_and_queries()
    engine = BatchPathEnum(backend="device", sharing="off",
                           chunk_size=CHUNK)
    launches = []
    real = kops.frontier_expand_fused

    def watched(paths, rank, *args, max_deg, **kw):
        out = real(paths, rank, *args, max_deg=max_deg, **kw)
        launches.append((paths.shape[0], max_deg, out))
        return out

    tables = []
    real_tables = kops.fused_tables

    def watched_tables(*args, **kw):
        tables.append(real_tables(*args, **kw))
        return tables[-1]

    trace_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kops, "frontier_expand_fused", watched)
        mp.setattr(kops, "fused_tables", watched_tables)
        before = trace.snapshot()
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        out = engine.run(g, qs, count_only=False)
        jax.profiler.stop_trace()
        after = trace.snapshot()
    assert out.fused_queries == len(qs) and out.fused_dispatches >= 2
    return out, trace.delta(after, before), launches, trace_dir, tables


def test_dispatch_and_sync_spans_count_the_fused_launches(fused_batch):
    out, d, launches, *_ = fused_batch
    assert len(launches) == out.fused_dispatches
    assert _spans(d, "pathenum.enum.dispatch")[1] == out.fused_dispatches
    assert _spans(d, "pathenum.enum.sync")[1] == out.fused_dispatches
    assert _spans(d, "pathenum.enum.fused")[1] == 1
    assert _spans(d, "pathenum.batch")[1] == 1
    assert _spans(d, "pathenum.plan")[1] == out.distinct_queries


def test_rounds_counter_equals_the_rounds_run(fused_batch):
    # every round pops one chunk from each live member, so the member
    # that finishes last popped one chunk in every round
    out, d, *_ = fused_batch
    rounds = max(it.result.stats.chunks for it in out.items)
    assert d["counters"]["pathenum.enum.rounds"] == rounds


def test_tables_are_built_once_per_fused_run(fused_batch):
    # one fused run: one build and one tables span, however many rounds
    # it serves
    _, d, *_, tables = fused_batch
    assert d["counters"]["pathenum.enum.rounds"] >= 2
    assert d["counters"]["pathenum.enum.table_builds"] == 1
    assert _spans(d, "pathenum.enum.tables")[1] == 1
    assert len(tables) == 1


def test_table_bytes_are_the_stacked_arrays(fused_batch):
    from repro.core import build_index
    _, d, *_, tables = fused_batch
    ((begin, end_all, dst),) = tables
    g, qs = _graph_and_queries()
    slots, k1max = _pow2(len(qs)), max(k for *_, k in qs) + 1
    mfm = max(build_index(g, s, t, k).device_arrays().dst.shape[0]
              for s, t, k in qs)
    assert begin.shape == (slots * g.n,)
    assert end_all.shape == (slots, k1max, g.n)
    assert dst.shape == (slots * mfm,)
    assert d["counters"]["pathenum.enum.table_bytes"] \
        == begin.nbytes + end_all.nbytes + dst.nbytes \
        == 4 * slots * (g.n * (k1max + 1) + mfm)


def test_slots_counter_is_the_launches_padded_rectangles(fused_batch):
    out, d, launches, *_ = fused_batch
    slots = sum(_pow2(max(rows, 8)) * _pow2(max_deg)
                for rows, max_deg, _ in launches)
    assert d["counters"]["pathenum.enum.slots"] == slots
    assert slots >= out.enum_stats.edges_accessed > 0


def test_d2h_bytes_are_the_copied_outputs_and_their_live_rows(fused_batch):
    _, d, launches, *_ = fused_batch
    g, qs = _graph_and_queries()
    copied = live = 0
    for _, _, (emit, cont, ne, nc, ctr) in launches:
        copied += sum(a.nbytes for a in (emit, cont, ne, nc, ctr))
        rows = int(np.asarray(ne).sum()) + int(np.asarray(nc).sum())
        live += rows * emit.shape[1] * 4 + ne.nbytes + nc.nbytes + ctr.nbytes
    # besides the launches' outputs, the misses' stacked BFS copies back
    # once its (2, rows, n) int8 distances, the (rows, KEPT_CAP) int32
    # ids of the edges each index keeps and their (rows,) int32 counts,
    # every row live here
    bfs_bytes = _pow2(len(qs)) * (2 * g.n + 4 * bfs.KEPT_CAP + 4)
    assert d["counters"]["pathenum.xfer.d2h_bytes"] == copied + bfs_bytes
    assert d["counters"]["pathenum.xfer.d2h_live_bytes"] == live + bfs_bytes
    assert 0 < live < copied
    assert d["counters"]["pathenum.xfer.h2d_bytes"] > 0
    assert d["counters"]["pathenum.enum.table_bytes"] > 0


def test_index_bfs_is_one_launch_over_one_graph_upload(fused_batch):
    # the batch's four misses: one stacked BFS launch of four rows, over
    # the graph's device copy, uploaded once for this graph object
    _, d, *_ = fused_batch
    g, qs = _graph_and_queries()
    c = d["counters"]
    assert c["pathenum.index.bfs_launches"] == 1
    assert c["pathenum.index.bfs_rows"] == _pow2(len(qs))
    assert c["pathenum.index.bfs_live_rows"] == len(qs)
    assert _spans(d, "pathenum.index.graph_upload")[1] == 1
    # the edge list, and per direction the padded predecessor lists and
    # each vertex's place in bucket order
    dev = g.device_arrays()
    slots = dev.fwd.pred.shape[0] + dev.rev.pred.shape[0]
    assert min(dev.fwd.pred.shape[0], dev.rev.pred.shape[0]) >= g.m
    upload = 4 * (2 * g.m + slots + 2 * g.n)
    assert c["pathenum.xfer.h2d_bytes"] >= upload + 3 * 4 * _pow2(len(qs))
    # kmax rounds a direction, each gathering the direction's slots once
    kmax = max(k for *_, k in qs)
    assert c["pathenum.index.bfs_gather_slots"] == kmax * slots


def test_graph_is_uploaded_once_per_version():
    g, qs = _graph_and_queries()
    engine = BatchPathEnum(backend="device", cache_capacity=0)
    before = trace.snapshot()
    engine.run(g, qs[:3], count_only=True)
    engine.run(g, qs[3:], count_only=True)
    g2 = g.add_edges(np.array([[0, 1]]))
    engine.run(g2, qs[:1], count_only=True)
    d = trace.delta(trace.snapshot(), before)
    c = d["counters"]
    assert _spans(d, "pathenum.index.graph_upload")[1] == 2
    assert c["pathenum.index.bfs_launches"] == 3
    # three misses ride in a launch of four rows
    assert c["pathenum.index.bfs_rows"] == 4 + 1 + 1
    assert c["pathenum.index.bfs_live_rows"] == 3 + 1 + 1
    assert g.device_arrays() is g.device_arrays()
    assert g2.device_arrays() is not g.device_arrays()


def test_graph_upload_is_once_under_racing_threads():
    g, _ = _graph_and_queries()
    got, before = [], trace.snapshot()
    barrier = threading.Barrier(8)

    def ask():
        barrier.wait(timeout=30)
        got.append(g.device_arrays())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    d = trace.delta(trace.snapshot(), before)
    assert len(got) == 8 and all(a is got[0] for a in got)
    assert _spans(d, "pathenum.index.graph_upload")[1] == 1


def test_host_backend_runs_no_device_bfs():
    g, qs = _graph_and_queries()
    before = trace.snapshot()
    BatchPathEnum(backend="host").run(g, qs, count_only=True)
    d = trace.delta(trace.snapshot(), before)
    assert "pathenum.index.bfs_launches" not in d["counters"]
    assert "pathenum.index.graph_upload" not in d["spans"]
    assert _spans(d, "pathenum.index.bfs")[1] == 1


def test_batch_timing_is_read_from_the_spans(fused_batch):
    out, d, *_ = fused_batch
    tm = out.timing
    assert tm.distance_seconds == pytest.approx(
        _spans(d, "pathenum.index.bfs")[0])
    assert tm.index_seconds == pytest.approx(
        _spans(d, "pathenum.index.build")[0])
    assert tm.optimize_seconds == pytest.approx(
        _spans(d, "pathenum.plan")[0])
    assert tm.enumerate_seconds == pytest.approx(
        sum(_spans(d, f"pathenum.enum.{p}")[0]
            for p in ("shared", "fused", "solo")))
    assert tm.enumerate_seconds > 0 and tm.index_seconds > 0
    assert tm.total_seconds >= _spans(d, "pathenum.batch")[0] > 0


def test_round_phases_lie_inside_the_fused_span(fused_batch):
    _, d, *_ = fused_batch
    phases = [_spans(d, f"pathenum.enum.{p}") for p in PHASES]
    assert all(calls > 0 for _, calls in phases)
    assert sum(s for s, _ in phases) <= _spans(d, "pathenum.enum.fused")[0]


def test_profiler_trace_holds_the_program_spans(fused_batch):
    sys.path.insert(0, str(REPO / "benchmarks"))
    from hcpe import devtrace
    *_, trace_dir, _ = fused_batch
    (path,) = pathlib.Path(trace_dir).glob("**/*.xplane.pb")
    names = {name for name, _, _ in devtrace.events(str(path))["host"]}
    want = {f"pathenum.enum.{p}" for p in PHASES} | {
        "pathenum.batch", "pathenum.plan", "pathenum.enum.fused",
        "pathenum.index.bfs", "pathenum.index.build",
        "pathenum.index.graph_upload"}
    assert want <= names


@pytest.mark.parametrize("deque", ["off", "on"])
def test_solo_device_drivers_use_the_same_names(deque, monkeypatch):
    from repro.core import build_index
    if deque == "off":
        monkeypatch.setenv("REPRO_DEVICE_DEQUE", "off")
    else:
        monkeypatch.delenv("REPRO_DEVICE_DEQUE", raising=False)
    g, _ = _graph_and_queries()
    idx = build_index(g, 0, 39, 4)
    before, d0 = trace.snapshot(), kops.device_dispatch_count()
    res = en.enumerate_paths_idx(idx, backend="device", chunk_size=CHUNK)
    d = trace.delta(trace.snapshot(), before)
    dispatches = kops.device_dispatch_count() - d0
    driver = "device_loop" if deque == "off" else "resident"
    assert d["counters"][en.DRIVER + driver] == 1
    assert _spans(d, "pathenum.enum.dispatch")[1] == dispatches > 0
    assert _spans(d, "pathenum.enum.sync")[1] == dispatches
    # the solo drivers slice on the device: all they copy back is live
    c = d["counters"]
    assert c["pathenum.xfer.d2h_live_bytes"] == c["pathenum.xfer.d2h_bytes"]
    assert c["pathenum.xfer.d2h_bytes"] >= 4 * 5 * res.count
    assert c["pathenum.enum.slots"] >= res.stats.edges_accessed


def test_old_counter_readers_read_the_tally():
    g, qs = _graph_and_queries()
    runs0, fan0 = dict(en.DRIVER_RUNS), kops.device_dispatch_fanouts()
    count0 = kops.device_dispatch_count()
    out = BatchPathEnum(backend="device", sharing="off").run(g, qs[:2])
    assert en.DRIVER_RUNS["fused"] - runs0.get("fused", 0) == 2
    assert en.DRIVER_RUNS["never_ran"] == 0
    assert set(en.DRIVER_RUNS) == set(dict(en.DRIVER_RUNS))
    fans = kops.device_dispatch_fanouts()
    moved = sum(v - fan0.get(b, 0) for b, v in fans.items())
    assert moved == kops.device_dispatch_count() - count0 \
        == out.fused_dispatches
    assert all(isinstance(b, int) and b == _pow2(b) for b in fans)


def test_tally_is_exact_under_async_servers_and_threads():
    """Two async servers on their own event-loop threads, their worker
    threads, and threads counting by hand all add to one tally: nothing
    is lost."""
    g, _ = _graph_and_queries()
    rng = np.random.default_rng(3)
    pairs = [tuple(int(v) for v in rng.choice(g.n, 2, replace=False))
             for _ in range(24)]
    servers = []

    async def drive(uid0):
        async with AsyncHcPEServer(g, batch_window_ms=0.5) as srv:
            servers.append(srv)
            for i in range(0, len(pairs), 4):
                await srv.serve([PathQueryRequest(uid=uid0 + i + j, s=s,
                                                  t=t, k=3)
                                 for j, (s, t) in
                                 enumerate(pairs[i:i + 4])])

    def hammer():
        for _ in range(2000):
            trace.count("pathenum.test.hammer")

    before = trace.snapshot()
    threads = [threading.Thread(target=asyncio.run, args=(drive(u),))
               for u in (0, 1000)]
    threads += [threading.Thread(target=hammer) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    d = trace.delta(trace.snapshot(), before)
    batches = sum(s.stats.micro_batches for s in servers)
    assert batches >= 12
    assert d["counters"]["pathenum.test.hammer"] == 8000
    assert _spans(d, "pathenum.batch")[1] == batches
    assert _spans(d, "pathenum.frontend.respond")[1] == batches
    assert _spans(d, "pathenum.frontend.window")[1] >= 2
    assert _spans(d, "pathenum.plan")[1] == sum(
        o.distinct_queries for s in servers for o in list(s._outputs))


def test_prometheus_export_carries_spans_and_counters(fused_batch):
    g, _ = _graph_and_queries()
    snap = snapshot(HcPEServer(g))
    lines = snap.to_prometheus().splitlines()
    secs, calls = snap.program["spans"]["pathenum.enum.sync"]
    assert f'pathenum_span_seconds_total{{span="pathenum.enum.sync"}} ' \
        f"{secs}" in lines
    assert f'pathenum_span_calls_total{{span="pathenum.enum.sync"}} ' \
        f"{calls}" in lines
    for name in ("enum.slots", "enum.rounds", "enum.table_bytes",
                 "enum.table_builds", "xfer.h2d_bytes", "xfer.d2h_bytes",
                 "xfer.d2h_live_bytes", "driver.fused",
                 "index.bfs_launches", "index.bfs_rows",
                 "index.bfs_live_rows", "index.bfs_gather_slots"):
        family = "pathenum_" + name.replace(".", "_") + "_total"
        assert f"# TYPE {family} counter" in lines
        assert f"{family} {snap.program['counters']['pathenum.' + name]}" \
            in lines
    headers = [ln for ln in lines if ln.startswith("# TYPE")]
    assert len(headers) == len(set(headers))
    assert snap.to_dict()["program"] == snap.program
