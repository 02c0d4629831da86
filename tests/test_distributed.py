"""Distributed runtime tests.

Multi-device behaviour (the shard_map engine and the tenant router)
needs >1 device, so those cases run in a subprocess with
``--xla_force_host_platform_device_count=8``, kept out of this process
so the rest of the suite sees one device.
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import json
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=540,
                         env={**os.environ, "PYTHONPATH": SRC})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_pathenum_matches_host():
    out = run_sub("""
        from repro.core import erdos_renyi, build_index, walk_count_dp
        from repro.distributed.engine import DistributedPathEnum
        from repro.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        g = erdos_renyi(60, 4.0, seed=5)
        k = 4
        eng = DistributedPathEnum(mesh, g, k)
        qs = []
        rng = np.random.default_rng(0)
        while len(qs) < 8:
            s, t = rng.integers(0, g.n, 2)
            if s != t: qs.append((int(s), int(t)))
        qp, qsx, tot, (ds, dt) = eng.query_batch_stats(np.array(qs))
        host = []
        for (s, t) in qs:
            idx = build_index(g, s, t, k)
            dp = walk_count_dp(idx)
            host.append((dp.q_prefix.tolist(), dp.q_suffix.tolist(),
                         dp.q_total))
        ok = True
        for i, (hp, hs, ht) in enumerate(host):
            ok &= np.allclose(qp[i], hp, rtol=1e-5)
            ok &= np.allclose(qsx[i], hs, rtol=1e-5)
            ok &= abs(tot[i] - ht) < 1e-4 * max(1.0, ht)
        print(json.dumps({"ok": bool(ok)}))
    """)
    assert json.loads(out.strip().splitlines()[-1])["ok"]


def test_distributed_tenant_router_matches_host_per_graph():
    """DESIGN.md §8, distributed leg: tagged (graph_id, s, t) queries
    route per-graph across the data axis through one shared host engine,
    counts byte-identical to per-graph host runs, cache tenant-keyed."""
    out = run_sub("""
        from repro.core import BatchPathEnum, PathEnum, erdos_renyi, power_law
        from repro.distributed.engine import (DistributedPathEnum,
                                              DistributedTenantRouter)
        from repro.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        k = 4
        g_a = erdos_renyi(50, 4.0, seed=5)
        g_b = power_law(60, 5.0, seed=9)
        engine = BatchPathEnum()
        router = DistributedTenantRouter(
            {"a": DistributedPathEnum(mesh, g_a, k),
             "b": DistributedPathEnum(mesh, g_b, k)}, engine=engine)
        rng = np.random.default_rng(1)
        tagged = []
        while len(tagged) < 10:
            s, t = rng.integers(0, 50, 2)
            if s != t:
                tagged.append((("a", "b")[len(tagged) % 2], int(s), int(t)))
        items, outputs = router.enumerate(tagged)
        seq = PathEnum()
        graphs = {"a": g_a, "b": g_b}
        ok = all(it.result.count == seq.count(graphs[gid], s, t, k)
                 for (gid, s, t), it in zip(tagged, items))
        unknown_raises = False
        try:
            router.enumerate([("ghost", 0, 1)])
        except KeyError:
            unknown_raises = True
        print(json.dumps({
            "ok": bool(ok),
            "tenants": sorted(outputs),
            "tenant_entries": [engine.cache.tenant_len("a") > 0,
                               engine.cache.tenant_len("b") > 0],
            "unknown_raises": unknown_raises}))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"]
    assert rec["tenants"] == ["a", "b"]
    assert rec["tenant_entries"] == [True, True]
    assert rec["unknown_raises"]
