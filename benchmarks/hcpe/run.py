"""The HcPE serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 benchmarks/hcpe/run.py --workload ep.k4_hot --seed 7 \\
        --seconds 10 --trace 0

A cell names a configuration (``configs/<name>.json``: the graph's
generator and sizes, the engine's and the server's settings) and a
traffic mix (``traffic/<name>.json``: the query pool, its bursts, the
serving options).  Each metric is read by ``metrics/<name>.py``.  The
run builds the graph and the pool from the seeds those files fix and the
order of the requests from ``--seed``, starts ``AsyncHcPEServer`` over
``BatchPathEnum`` on one chip, warms up every shape the cell's traffic
uses (set-up), serves the traffic for ``--seconds`` (the window), and
then checks the window's answers against the plain reference
(``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones, read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared, with its limit.  The same numbers are
the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, the run exits with 1 and prints no result.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import gc
import importlib.util
import json
import os
import pathlib
import resource
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

T_START = time.perf_counter()

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(CHECKOUT / "src"))

import numpy as np  # noqa: E402

from hcpe import devtrace, gen, reference  # noqa: E402

# switches that route the served path around the device kernels
HIDING_SWITCHES = ("REPRO_PALLAS", "REPRO_DEVICE_ENUM", "REPRO_DEVICE_DEQUE",
                   "REPRO_SHARING")
SUBMIT_SPAN = "hcpe.submit"
BATCH_SPAN = "hcpe.batch"
# an answer may arrive this long after the window closes; later is missing
GRACE_S = 60.0


class BenchError(Exception):
    """The run cannot be made as the cell asks."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic mix
    and the metric readers a run reports."""

    def __init__(self, root: pathlib.Path, name: str, trace: bool) -> None:
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.spec = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = json.loads(
            (root / configs[self.spec["config"]]["file"]).read_text())
        bench_dir = root / "benchmarks" / "hcpe"
        self.traffic = json.loads(
            (bench_dir / "traffic" / f"{self.spec['traffic']}.json")
            .read_text())
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        if trace:
            moved = {m["name"] for m in e2e}
            chosen = [m for m in bench["per_layer"]
                      if (name in m["workloads"] if "workloads" in m
                          else m["moves"] in moved)]
        else:
            chosen = e2e
        self.metrics = [(m["name"], m["unit"],
                         _reader(bench_dir / "metrics" / f"{m['name']}.py"))
                        for m in chosen]
        self.peaks = json.loads((bench_dir / "peaks.json").read_text())

    @property
    def k(self) -> int:
        """The hop bound of every query of the mix."""
        return int(self.traffic["k"])


def _reader(path: pathlib.Path):
    """The ``read(record)`` function of one metric's file."""
    spec = importlib.util.spec_from_file_location(
        "hcpe_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise BenchError(f"no metric reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# counters the program exposes, and JAX's compile events
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compiles seen through ``jax.monitoring`` (a persistent
    cache read counts as one, with its retrieval time), and cache hits."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.seconds: List[float] = []
        self.names: List[str] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == self.EVENT:
            self.seconds.append(secs)
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **_kw: Any) -> None:
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> Tuple[int, int]:
        """A position to take ``since`` from."""
        return len(self.seconds), self.hits

    def since(self, mark: Tuple[int, int]) -> Dict[str, float]:
        """Compiles, their seconds and cache hits after ``mark``."""
        new = self.seconds[mark[0]:]
        return {"count": len(new), "seconds": float(sum(new)),
                "cache_hits": self.hits - mark[1],
                "names": dict(collections.Counter(self.names[mark[0]:]))}


def program_counters() -> Dict[str, Any]:
    """Frontier dispatches (total and per fan-out bucket) and driver runs
    since the process started."""
    from repro.core.enumerate import DRIVER_RUNS
    from repro.kernels import ops
    return {"dispatches": ops.device_dispatch_count(),
            "fanouts": ops.device_dispatch_fanouts(),
            "drivers": dict(DRIVER_RUNS)}


def counter_delta(before: Dict[str, Any], after: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """``after - before`` of ``program_counters`` readings."""
    def sub(a: Dict, b: Dict) -> Dict:
        return {key: v - b.get(key, 0) for key, v in a.items()
                if v != b.get(key, 0)}
    return {"dispatches": after["dispatches"] - before["dispatches"],
            "fanouts": sub(after["fanouts"], before["fanouts"]),
            "drivers": sub(after["drivers"], before["drivers"])}


def make_engine(settings: Dict[str, Any], csr: gen.Csr):
    """The program's ``BatchPathEnum`` with the configuration's
    ``settings``, a profiler span around each micro-batch and a plain
    summary of each batch it serves (``csr``, the benchmark's copy of the
    graph, is for engines that stand in for the program)."""
    import jax
    from repro.core import BatchPathEnum

    class BenchEngine(BatchPathEnum):
        """``BatchPathEnum`` that records what each ``run`` did."""

        def __init__(self, **kw: Any) -> None:
            super().__init__(**kw)
            self.batches: List[Dict[str, Any]] = []

        def run(self, graph, queries, *args: Any, **kw: Any):
            """``BatchPathEnum.run`` inside the batch span."""
            with jax.profiler.TraceAnnotation(BATCH_SPAN):
                out = super().run(graph, queries, *args, **kw)
            self.batches.append(summarize(out))
            return out

    return BenchEngine(**settings)


def summarize(out) -> Dict[str, Any]:
    """The numbers of one ``BatchOutput`` that metrics read."""
    seen = set()
    items = []
    for it in out.items:
        if id(it.result) in seen:
            continue
        seen.add(id(it.result))
        st = it.result.stats
        items.append({"plan": it.plan.method, "k": it.k,
                      "edges_accessed": int(st.edges_accessed),
                      "partials_generated": int(st.partials_generated),
                      "results": int(st.results), "fused": bool(it.fused),
                      "shared": bool(it.shared)})
    tm = out.timing
    return {"queries": len(out.items), "distinct": int(out.distinct_queries),
            "distance_s": tm.distance_seconds, "index_s": tm.index_seconds,
            "optimize_s": tm.optimize_seconds,
            "enumerate_s": tm.enumerate_seconds, "total_s": tm.total_seconds,
            "hits": int(out.cache_stats.hits),
            "misses": int(out.cache_stats.misses),
            "fused_queries": int(out.fused_queries), "items": items}


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

class Traffic:
    """What the cell's caller asks: the pool of queries drawn from the
    mix's ``pool_seed``, dealt into bursts of equal work, and the order of
    the bursts drawn from the run's seed.  Warm-up serves every burst
    once, so that each index is built, and then once more as the window
    will (all indexes cached): a burst is one micro-batch, so the window
    meets no shape that warm-up did not compile."""

    def __init__(self, csr: gen.Csr, mix: Dict[str, Any], seed: int
                 ) -> None:
        self.pool = gen.sample_pairs(
            csr, int(mix["pool"]), int(mix["k"]), int(mix["max_dist"]),
            gen.rng_for(int(mix["pool_seed"]), gen.POOL))
        self.bursts = gen.deal_bursts(csr, self.pool, int(mix["burst"]))
        self.warmup = self.bursts + self.bursts
        order = gen.burst_order(len(self.bursts), int(mix["cycles"]),
                                gen.rng_for(seed, gen.ORDER))
        self.window = [self.bursts[b] for b in order]


class Client:
    """Sends the mix's bursts through ``AsyncHcPEServer.submit`` and logs,
    on the client's clock, when each request was sent and when its
    answer came."""

    def __init__(self, server, mix: Dict[str, Any]) -> None:
        self.server = server
        self.count_only = bool(mix["count_only"])
        self.uid = 0

    async def ask(self, q: Tuple[int, int, int],
                  log: List[Dict[str, Any]]) -> None:
        """One request, logged as {query, sent, done, resp}."""
        import jax
        from repro.serving import PathQueryRequest
        self.uid += 1
        req = PathQueryRequest(uid=self.uid, s=q[0], t=q[1], k=q[2],
                               count_only=self.count_only)
        entry: Dict[str, Any] = {"query": q, "sent": time.perf_counter(),
                                 "done": None, "resp": None}
        log.append(entry)
        with jax.profiler.TraceAnnotation(SUBMIT_SPAN):
            entry["resp"] = await self.server.submit(req)
        entry["done"] = time.perf_counter()

    async def bursts(self, bursts: List[List[Tuple[int, int, int]]],
                     until: Optional[float], log: List[Dict[str, Any]]
                     ) -> None:
        """Each burst's requests sent together, the next burst when all
        of them are answered, until ``until`` (or the list runs out).  A
        burst still unanswered ``GRACE_S`` after ``until`` is given up:
        its requests stay without an answer."""
        for burst in bursts:
            if until is not None and time.perf_counter() >= until:
                return
            try:
                await asyncio.wait_for(
                    asyncio.gather(*(self.ask(q, log) for q in burst)),
                    None if until is None
                    else until - time.perf_counter() + GRACE_S)
            except asyncio.TimeoutError:
                return
        if until is not None:
            raise BenchError("the request stream ran out inside the window")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def check_environment() -> None:
    """Refuse the switches that route around the device kernels."""
    for var in HIDING_SWITCHES:
        if var in os.environ:
            raise BenchError(f"{var} is set; it can route the served path "
                             f"around the device kernels")


def use_cache() -> str:
    """JAX's persistent compilation cache in its fixed directory of the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), keeping every program:
    many frontier compiles take less than JAX's one-second default."""
    import jax
    from repro.compile_cache import use_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return use_compile_cache()


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    """The devices as JAX reports them; a TPU with ``chips`` chips or
    more is required unless ``require_tpu`` is off (harness tests)."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchError(f"platform is {d0.platform}, not tpu")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} devices, the cell needs {chips}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = CHECKOUT, require_tpu: bool = True,
             engine_factory=make_engine, log=print) -> Dict[str, Any]:
    """One run of cell ``name``; returns the result line's object."""
    check_environment()
    cell = Cell(root, name, trace)
    if cell.config["engine"].get("backend") != "device":
        raise BenchError("the served path under test is backend='device'")
    cache_dir = use_cache()
    import jax
    device = device_info(int(cell.spec["chips"]), require_tpu)
    peak = cell.peaks.get(device["kind"])
    if require_tpu and peak is None:
        raise BenchError(f"no peaks for device kind {device['kind']!r}")
    log(f"device {device} compile cache {cache_dir}")
    compiles = CompileLog()

    from repro.core import from_edges
    from repro.serving import AsyncHcPEServer
    t0 = time.perf_counter()
    n = int(cell.config["graph"]["n"])
    edges = gen.build_edges(cell.config["graph"])
    graph = from_edges(n, edges)
    csr = gen.Csr.from_edges(n, edges)
    del edges
    log(f"graph n={graph.n} m={graph.m} built in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    mix = cell.traffic
    traffic = Traffic(csr, mix, seed)
    log(f"traffic pool={len(traffic.pool)} bursts={len(traffic.bursts)} "
        f"drawn in {time.perf_counter() - t0:.3f} s")

    engine = engine_factory(cell.config["engine"], csr)
    trace_dir = HERE / ".traces" / f"{name}-{seed}"
    state: Dict[str, Any] = {}

    async def serve() -> None:
        async with AsyncHcPEServer(graph, engine,
                                   **cell.config["server"]) as server:
            client = Client(server, mix)
            t1 = time.perf_counter()
            warm_log: List[Dict[str, Any]] = []
            await client.bursts(traffic.warmup, None, warm_log)
            bad = [e for e in warm_log
                   if e["resp"] is None or e["resp"].status != "ok"]
            if bad:
                raise BenchError(f"{len(bad)} warm-up requests failed")
            log(f"warm-up {len(warm_log)} requests in "
                f"{time.perf_counter() - t1:.3f} s, "
                f"compiles {compiles.since((0, 0))}")
            state["batches0"] = len(engine.batches)
            state["counters0"] = program_counters()
            state["compiles0"] = compiles.mark()
            state["cache0"] = engine.cache.stats.snapshot()
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
            window_log: List[Dict[str, Any]] = []
            start = time.perf_counter()
            state["setup_s"] = start - T_START
            await client.bursts(traffic.window, start + seconds, window_log)
            if trace:
                jax.profiler.stop_trace()
            state["start"] = start
            state["log"] = window_log
            state["cache1"] = engine.cache.stats.snapshot()
            state["counters1"] = program_counters()
            state["compiles"] = compiles.since(state["compiles0"])

    asyncio.run(serve())
    window_log = state["log"]
    device["memory_peak_bytes"] = memory_peak(int(cell.spec["chips"]))
    start = state["start"]
    for e in window_log:
        e["ok"] = e["resp"] is not None and e["resp"].status == "ok"
    ok = [e for e in window_log if e["ok"]]
    cache = state["cache1"].delta(state["cache0"])
    record: Dict[str, Any] = {
        "cell": name, "k": cell.k, "traffic": mix,
        "setup_s": state["setup_s"],
        "window_s": max(e["done"] for e in ok) - start if ok else seconds,
        "requests": [{"sent": e["sent"] - start, "ok": e["ok"]}
                     for e in window_log],
        "batches": engine.batches[state["batches0"]:],
        "cache": {"hits": cache.hits, "misses": cache.misses},
        "compiles": state["compiles"],
        "peak": peak, "trace": None,
    }
    record.update(counter_delta(state["counters0"], state["counters1"]))
    index_bytes = device_index_bytes(engine)
    del engine, graph
    gc.collect()

    if trace:
        record["trace"] = devtrace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    diagnostics(record, index_bytes, log)

    t0 = time.perf_counter()
    checks, checked = check_answers(csr, window_log, mix, seed)
    log(f"reference check of {checked} answers in "
        f"{time.perf_counter() - t0:.3f} s")
    metrics = {}
    for mname, unit, read in cell.metrics:
        value = read(record)
        if value is not None:
            metrics[mname] = {"value": float(value), "unit": unit}
    out: Dict[str, Any] = {
        "correct": checked > 0 and all(v["value"] <= v["limit"]
                                       for v in checks.values()),
        "attempted": len(window_log),
        "failed": len(window_log) - len(ok),
        "metrics": metrics, "device": device}
    tr = record["trace"]
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    return out


def device_index_bytes(engine) -> List[int]:
    """Device bytes of each cached index that went to the device."""
    sizes = []
    for idx in getattr(engine.cache, "_entries", {}).values():
        dev = idx.__dict__.get("_device_arrays")
        if dev is not None:
            sizes.append(sum(int(a.nbytes) for a in
                             (dev.begin, dev.end, dev.dst)))
    return sizes


def diagnostics(rec: Dict[str, Any], index_bytes: List[int], log) -> None:
    """Lines for the reader of the run's log (not compared, not metrics)."""
    log(f"window {rec['window_s']:.3f} s requests={len(rec['requests'])} "
        f"batches={len(rec['batches'])} drivers={rec['drivers']} "
        f"dispatches={rec['dispatches']} fanouts={rec['fanouts']}")
    log(f"window compiles {rec['compiles']}")
    log(f"host peak rss bytes "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
    if index_bytes:
        log(f"device index bytes per cached index: mean="
            f"{np.mean(index_bytes):.0f} max={max(index_bytes)} "
            f"indexes={len(index_bytes)}")


def check_answers(csr: gen.Csr, window_log: List[Dict[str, Any]],
                  mix: Dict[str, Any], seed: int
                  ) -> Tuple[Dict[str, Dict[str, int]], int]:
    """Compare the window's answers with the plain reference.

    Every request of the window has to be answered ``ok``: the window
    ends when the last burst sent in it is answered.  Of the distinct
    queries answered, a sample drawn from the seed (the one with the most
    results always in it) is enumerated by the reference, and every
    answer to a sampled query is compared: its count, and the set of its
    paths where the mix returns them.  Returns each compared number with
    its limit, and how many answers were compared."""
    unanswered = sum(1 for e in window_log
                     if e["resp"] is None or e["resp"].status != "ok")
    answered = [e for e in window_log
                if e["resp"] is not None and e["resp"].status == "ok"]
    queries = sorted({e["query"] for e in answered})
    biggest = max(answered, key=lambda e: e["resp"].count)["query"] \
        if answered else None
    rng = gen.rng_for(seed, gen.SAMPLE)
    sample = [queries[i] for i in rng.permutation(len(queries))]
    sample = ([biggest] + [q for q in sample if q != biggest]
              )[:int(mix["check_queries"])] if answered else []
    want_paths = not mix["count_only"]
    wrong_count = wrong_paths = checked = 0
    for q in sample:
        ref = reference.paths(csr, *q)
        for e in answered:
            if e["query"] != q:
                continue
            resp = e["resp"]
            checked += 1
            wrong_count += int(resp.count != ref.shape[0])
            if want_paths:
                wrong_paths += int(
                    resp.paths is None or resp.paths.shape != ref.shape
                    or not np.array_equal(reference.sort_rows(resp.paths),
                                          ref))
    checks = {"unanswered": unanswered, "wrong_count": wrong_count}
    if want_paths:
        checks["wrong_paths"] = wrong_paths
    return {key: {"value": v, "limit": 0} for key, v in checks.items()}, \
        checked


def report(out: Dict[str, Any]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for key, v in out["checks"].items():
        print(f"check {key} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    """Parse the driver's arguments and make one run."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise BenchError("--seed must not be negative")

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   log=log)
    report(out)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"hcpe benchmark: {e}", file=sys.stderr)
        sys.exit(1)
