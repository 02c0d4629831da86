"""Smoke test of the served HcPE path on a TPU.

    python chip_smoke.py             # one chip: the served path
    python chip_smoke.py --chips 4   # four chips: the sharded mesh path

One chip: a 10^6-vertex power-law graph (edges made symmetric, about
4.3M directed edges after dedup) and 6-8 §7.1-style queries, served by
``AsyncHcPEServer(graph, backend="device")`` as one fused micro-batch
(cold, then warm), as a fused micro-batch that returns paths, and one
query at a time (the resident deque or the host-looped device driver).
Every response is checked against ``BatchPathEnum(backend="host")``
(counts, path arrays, ``EnumStats``), one query against the
backtracking oracle, and every device query must have issued frontier
kernel dispatches.  Each phase also logs its dispatches per fan-out
bucket and its backend compiles (count, seconds, persistent-cache hits,
and the seconds of each frontier jit's compile).

Four chips: ``DistributedPathEnum.enumerate_batch`` on a (data=2,
model=2) mesh over the same graph and queries; counts must equal the
host engine's and the mesh BFS distances the host BFS.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Any failed check, a platform other than ``tpu``, or one of the
environment switches that route around the kernels exits non-zero
without that line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

SEED = 0
N_VERTICES = 1_000_000
AVG_DEGREE = 8.0
N_QUERIES = (6, 8)              # at least, at most
K_RANGE = (4, 5, 6)
EST_RANGE = (1e3, 1e7)          # estimated result count of a kept query
MAX_DIST = 3                    # §7.1: dist(s, t) <= 3
N_PATH_QUERIES = 2              # served with count_only=False
MAX_CANDIDATE_BLOCKS = 16       # sources tried: 16 per block
HIDING_SWITCHES = ("REPRO_PALLAS", "REPRO_DEVICE_ENUM", "REPRO_DEVICE_DEQUE")


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compiles seen through ``jax.monitoring``: one
    ``(jit name, seconds)`` entry per compile (a persistent-cache read
    counts as one, with its retrieval time), and the cache hits."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.compiles: list = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == self.EVENT:
            self.compiles.append((str(kw.get("fun_name", "?")), secs))

    def _event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> tuple:
        return len(self.compiles), self.hits

    def summary(self, since: tuple = (0, 0)) -> str:
        """Compiles after ``since`` (a ``mark()``): count, seconds, cache
        hits, and per frontier jit its compiles' seconds in order."""
        new = self.compiles[since[0]:]
        per: dict = {}
        for name, secs in new:
            if "frontier" in name or "deque" in name:
                per.setdefault(name, []).append(f"{secs:.3f}")
        jits = " ".join(f"{n}=[{','.join(v)}]" for n, v in sorted(per.items()))
        return (f"compiles={len(new)} compile_s={sum(s for _, s in new):.3f} "
                f"cache_hits={self.hits - since[1]} {jits}").rstrip()


def fanout_delta(before: dict) -> str:
    """Frontier dispatches per pow2 fan-out bucket since ``before`` (an
    ``ops.device_dispatch_fanouts()``), and the largest bucket."""
    from repro.kernels import ops
    now = ops.device_dispatch_fanouts()
    hit = {b: c - before.get(b, 0) for b, c in sorted(now.items())
           if c != before.get(b, 0)}
    return f"fanout_buckets={hit} max_fanout_bucket={max(hit, default=0)}"


# ---------------------------------------------------------------------------
# graph and queries (host, from the seed)
# ---------------------------------------------------------------------------

def build_graph():
    """``power_law(N_VERTICES, AVG_DEGREE, seed=SEED)`` with every edge
    also added reversed.  The generator draws out- and in-endpoints from
    independent Zipf permutations, so its hubs are pure sources or pure
    sinks and no query has 10^3 paths; the symmetric graph keeps the
    degree distribution and has the long hub-to-hub paths of a social
    or web graph."""
    import numpy as np
    from repro.core import from_edges, power_law
    g0 = power_law(N_VERTICES, AVG_DEGREE, seed=SEED)
    e = np.stack([g0.esrc, g0.edst], axis=1)
    return from_edges(g0.n, np.concatenate([e, e[:, ::-1]]))


def pick_queries(g):
    """6-8 ``(s, t, k, est, index)`` queries: endpoints from the
    top 10% by degree with dist(s, t) <= 3, k in K_RANGE, a dfs plan, an
    estimated result count in EST_RANGE, and no endpoint shared between
    queries (so the micro-batch fuses instead of sharing a walk)."""
    import numpy as np
    from repro.core import build_index, plan_query, walk_count_dp
    from repro.core.batch import batched_bounded_bfs, batched_index_distances

    deg = np.diff(g.indptr)
    vprime = np.nonzero(deg >= max(np.quantile(deg, 0.9), 1))[0]
    in_vprime = np.zeros(g.n, bool)
    in_vprime[vprime] = True
    rng = np.random.default_rng(SEED)
    used: set = set()
    queries = []
    for _ in range(MAX_CANDIDATE_BLOCKS):
        srcs = rng.choice(vprime, 16, replace=False)
        near = batched_bounded_bfs(g.rindptr, g.rindices, g.n, srcs,
                                   np.full(16, -1), MAX_DIST)
        pairs = []
        for row, s in enumerate(srcs):
            cand = np.nonzero(in_vprime & (near[row] >= 1)
                              & (near[row] <= MAX_DIST))[0]
            if cand.size:
                pairs.append((int(s), int(rng.choice(cand))))
        dists = batched_index_distances(
            g, [(s, t, max(K_RANGE)) for s, t in pairs], block=16)
        for (s, t), (ds, dt) in zip(pairs, dists):
            if s in used or t in used:
                continue
            # rotate the preferred k so the set spans the range
            ks = np.roll(K_RANGE, -len(queries))
            for k in (int(x) for x in ks):
                d = (np.minimum(ds, k + 1).astype(np.int32),
                     np.minimum(dt, k + 1).astype(np.int32))
                idx = build_index(g, s, t, k, dist_fn=lambda *_a, _d=d: _d)
                plan = plan_query(idx, backend="host")
                est = (plan.est_results if plan.est_results is not None
                       else walk_count_dp(idx).q_total)
                if plan.method == "dfs" and \
                        EST_RANGE[0] <= est <= EST_RANGE[1]:
                    queries.append((s, t, k, est, idx))
                    used.update((s, t))
                    break
            if len(queries) == N_QUERIES[1]:
                return queries
    check(len(queries) >= N_QUERIES[0],
          f"only {len(queries)} queries passed the filters")
    return queries


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------

def run_one_chip(g, queries, compiles: CompileLog) -> None:
    import numpy as np
    from repro.core import BatchPathEnum, EnumStats, oracle
    from repro.core.enumerate import DRIVER_RUNS
    from repro.kernels import ops
    from repro.serving import AsyncHcPEServer, PathQueryRequest

    triples = [(s, t, k) for s, t, k, _, _ in queries]
    # the smallest estimates return paths (and one goes to the oracle)
    by_est = sorted(range(len(queries)), key=lambda i: queries[i][3])
    path_ids = set(by_est[:N_PATH_QUERIES])

    t0 = time.perf_counter()
    host = BatchPathEnum(backend="host").run(g, triples, count_only=False)
    log(f"host reference: {time.perf_counter() - t0:.3f} s")
    ref = {i: item.result for i, item in enumerate(host.items)}
    total = 0
    for i, (s, t, k, est, idx) in enumerate(queries):
        item = host.items[i]
        dev = idx.device_arrays()
        nbytes = sum(a.nbytes for a in (dev.begin, dev.end, dev.dst))
        total += nbytes
        log(f"query {i}: s={s} t={t} k={k} est={est:.6g} "
            f"plan={item.plan.method} paths={item.result.count} "
            f"index_edges={idx.num_index_edges} "
            f"device_index_bytes={nbytes}")
        check(item.plan.method == "dfs", f"query {i} planned {item.plan}")
        check(item.result.count > 0, f"query {i} has no paths")
    log(f"device index bytes, all queries: {total}")

    def merged(ids):
        agg = EnumStats()
        for i in ids:
            agg.merge(ref[i].stats)
        return agg

    def check_responses(name, ids, resps):
        for i, r in zip(ids, resps):
            check(r.status == "ok", f"{name} query {i}: status {r.status}")
            check(r.count == ref[i].count,
                  f"{name} query {i}: count {r.count} != {ref[i].count}")
            if r.paths is not None:
                check(np.array_equal(r.paths, ref[i].paths),
                      f"{name} query {i}: paths differ from the host")

    def request(uid, i, count_only=True):
        s, t, k = triples[i]
        return PathQueryRequest(uid=uid, s=s, t=t, k=k,
                                count_only=count_only)

    async def serve() -> None:
        async with AsyncHcPEServer(g, backend="device") as server:
            all_ids = list(range(len(triples)))
            for phase in ("fused cold", "fused warm"):
                d0 = ops.device_dispatch_count()
                b0 = ops.device_dispatch_fanouts()
                f0 = DRIVER_RUNS["fused"]
                c0 = compiles.mark()
                t1 = time.perf_counter()
                resps = await server.serve([request(i, i) for i in all_ids])
                wall = time.perf_counter() - t1
                rep = server.drain_report()
                dd = ops.device_dispatch_count() - d0
                nf = DRIVER_RUNS["fused"] - f0
                log(f"{phase}: {len(all_ids)} queries, driver=fused "
                    f"members={nf} dispatches={dd} {fanout_delta(b0)} "
                    f"wall={wall:.3f} s")
                log(f"{phase}: {compiles.summary(c0)}")
                check_responses(phase, all_ids, resps)
                check(nf == len(all_ids),
                      f"{phase}: {nf} of {len(all_ids)} queries fused")
                check(dd > 0, f"{phase}: no kernel dispatch")
                check(rep.enum_stats == merged(all_ids),
                      f"{phase}: EnumStats {rep.enum_stats} != host "
                      f"{merged(all_ids)}")

            pids = sorted(path_ids)
            d0 = ops.device_dispatch_count()
            b0 = ops.device_dispatch_fanouts()
            f0 = DRIVER_RUNS["fused"]
            c0 = compiles.mark()
            t1 = time.perf_counter()
            resps = await server.serve(
                [request(i, i, count_only=False) for i in pids])
            wall = time.perf_counter() - t1
            rep = server.drain_report()
            dd = ops.device_dispatch_count() - d0
            log(f"fused paths: queries {pids}, driver=fused "
                f"members={DRIVER_RUNS['fused'] - f0} dispatches={dd} "
                f"{fanout_delta(b0)} wall={wall:.3f} s")
            log(f"fused paths: {compiles.summary(c0)}")
            check_responses("fused paths", pids, resps)
            check(all(r.paths is not None for r in resps),
                  "fused paths: no path arrays returned")
            check(DRIVER_RUNS["fused"] - f0 == len(pids),
                  "fused paths: not every query fused")
            check(dd > 0, "fused paths: no kernel dispatch")
            check(rep.enum_stats == merged(pids),
                  "fused paths: EnumStats differ from the host")

            for i in all_ids:
                before = dict(DRIVER_RUNS)
                d0 = ops.device_dispatch_count()
                b0 = ops.device_dispatch_fanouts()
                c0 = compiles.mark()
                t1 = time.perf_counter()
                resp = await server.submit(
                    request(100 + i, i, count_only=i not in path_ids))
                wall = time.perf_counter() - t1
                rep = server.drain_report()
                dd = ops.device_dispatch_count() - d0
                ran = [d for d, c in DRIVER_RUNS.items()
                       if c != before.get(d, 0)]
                log(f"solo query {i}: plan={resp.plan_method} "
                    f"driver={'+'.join(ran) or 'none'} dispatches={dd} "
                    f"{fanout_delta(b0)} "
                    f"paths={'yes' if resp.paths is not None else 'no'} "
                    f"wall={wall:.3f} s")
                log(f"solo query {i}: {compiles.summary(c0)}")
                check_responses(f"solo {i}", [i], [resp])
                check(dd > 0, f"solo query {i}: no kernel dispatch")
                check(ran and "host" not in ran,
                      f"solo query {i}: ran on {ran}")
                check(rep.enum_stats == ref[i].stats,
                      f"solo query {i}: EnumStats {rep.enum_stats} != "
                      f"host {ref[i].stats}")

    asyncio.run(serve())

    i = by_est[0]
    s, t, k = triples[i]
    t1 = time.perf_counter()
    want = oracle.enumerate_paths(g, s, t, k)
    got = sorted(ref[i].as_tuples())
    log(f"oracle: query {i} ({len(want)} paths) in "
        f"{time.perf_counter() - t1:.3f} s")
    check(got == want, f"query {i}: host paths differ from the oracle")


# ---------------------------------------------------------------------------
# four chips: the sharded mesh path
# ---------------------------------------------------------------------------

def run_four_chips(g, queries, devices) -> None:
    import numpy as np
    from repro.compat import make_mesh
    from repro.core import BatchPathEnum
    from repro.core.batch import batched_index_distances
    from repro.distributed.engine import DistributedPathEnum

    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    for k in sorted({q[2] for q in queries}):
        pairs = np.array([(s, t) for s, t, kk, _, _ in queries if kk == k],
                         np.int64)
        dpe = DistributedPathEnum(mesh, g, k)
        shards = sorted((sh.device.id, sh.index[0].start or 0,
                         sh.data.shape[0])
                        for sh in dpe.esrc.addressable_shards)
        log(f"k={k}: edge shards (device, first edge, edges): {shards}")
        check(len({d for d, _, _ in shards}) == 4,
              f"edges sit on {len(shards)} devices, not 4")
        t0 = time.perf_counter()
        out = dpe.enumerate_batch(pairs, count_only=True,
                                  engine=BatchPathEnum(backend="host"))
        wall = time.perf_counter() - t0
        triples = [(int(s), int(t), k) for s, t in pairs]
        host = BatchPathEnum(backend="host").run(g, triples)
        log(f"k={k}: {len(triples)} queries on the mesh in {wall:.3f} s, "
            f"counts {out.counts.tolist()}")
        check(out.counts.tolist() == host.counts.tolist(),
              f"k={k}: mesh counts {out.counts.tolist()} != host "
              f"{host.counts.tolist()}")
        padded = pairs if pairs.shape[0] % 2 == 0 else \
            np.concatenate([pairs, pairs[:1]])
        _, _, _, (ds, dt) = dpe.query_batch_stats(padded)
        for row, want in enumerate(batched_index_distances(g, triples)):
            check(np.array_equal(ds[row], want[0])
                  and np.array_equal(dt[row], want[1]),
                  f"k={k}: mesh BFS distances differ for {triples[row]}")
        log(f"k={k}: mesh BFS distances equal the host BFS")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    for var in HIDING_SWITCHES:
        check(var not in os.environ,
              f"{var} is set; it can route the device path around the "
              f"kernels")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"devices: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}")
    check(d0.platform == "tpu", f"platform is {d0.platform}, not tpu")
    check(len(devices) >= args.chips,
          f"{len(devices)} devices, {args.chips} needed")
    log(f"compile cache: {cache_dir}")
    compiles = CompileLog()

    t0 = time.perf_counter()
    g = build_graph()
    csr = sum(a.nbytes for a in (g.indptr, g.indices, g.rindptr,
                                 g.rindices, g.esrc))
    log(f"graph: n={g.n} m={g.esrc.shape[0]} csr_bytes={csr} built in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    queries = pick_queries(g)
    log(f"queries: {len(queries)} picked in {time.perf_counter() - t0:.3f} s")

    if args.chips == 4:
        run_four_chips(g, queries, devices[:4])
    else:
        run_one_chip(g, queries, compiles)
    log(f"whole run: {compiles.summary()}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
