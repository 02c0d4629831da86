"""Batch **HcPE** serving demo: dedup + warm index cache, sync front-end.

    PYTHONPATH=src python examples/batch_serving.py

Builds a hub-heavy graph, simulates a production query log (many requests
hitting a small set of hot s-t pairs), serves it twice through HcPEServer
and prints the serving report — throughput, latency percentiles, and the
index-cache reuse that makes the second batch cheap.

``HcPEServer(g)`` here is the single-graph convenience form: the bare
graph wraps into a one-tenant ``GraphRegistry`` under the default
``graph_id`` (DESIGN.md §8), so this demo is byte-identical to the
pre-tenancy server.

Siblings:
  * examples/async_serving.py — the **async** HcPE front-end
    (AsyncHcPEServer: admission control + deadline-aware micro-batching)
    layered over the same engine this demo drives synchronously.
  * examples/multi_tenant_serving.py — the **multi-graph** registry flow
    (GraphRegistry: many tenant graphs, per-tenant quotas/stats) over
    both front-ends.
"""
import numpy as np

from repro.core import BatchPathEnum, PathEnum, power_law
from repro.serving import HcPEServer, PathQueryRequest

g = power_law(2000, 6.0, seed=3)
k = 4

# hot query pool: high-degree endpoints (the paper's V' sets, §7.1)
deg = np.diff(g.indptr)
hubs = np.argsort(deg)[-40:]
rng = np.random.default_rng(0)
pool = []
while len(pool) < 10:
    s, t = rng.choice(hubs, 2, replace=False)
    if (int(s), int(t)) not in pool:
        pool.append((int(s), int(t)))

# a 50-request batch over 10 hot pairs -> 80% duplicates
requests = [PathQueryRequest(uid=i, s=pool[j][0], t=pool[j][1], k=k)
            for i, j in enumerate(rng.integers(0, len(pool), size=50))]

server = HcPEServer(g, BatchPathEnum())
responses, report = server.serve(requests)
print(f"cold batch: {report.batch_size} queries "
      f"({report.distinct_queries} distinct), "
      f"{report.total_results} paths, "
      f"{report.throughput_qps:,.0f} q/s")
print(f"  latency p50={report.p50_ms:.3f}ms p90={report.p90_ms:.3f}ms "
      f"p99={report.p99_ms:.3f}ms")
print(f"  index cache: {report.cache.hits} hits / "
      f"{report.cache.misses} misses (hit rate "
      f"{report.cache.hit_rate:.0%})")

# same workload again: every index now comes out of the warm LRU
responses2, report2 = server.serve(requests)
print(f"warm batch: {report2.throughput_qps:,.0f} q/s, "
      f"hit rate {report2.cache.hit_rate:.0%}")

# counts must be byte-identical to the sequential engine
seq = PathEnum()
for r in responses:
    req = requests[r.uid]
    assert r.count == seq.count(g, req.s, req.t, req.k)
print(f"sequential cross-check: OK ({len(responses)} responses)")
