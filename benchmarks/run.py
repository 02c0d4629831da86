"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only substr]

Prints ``name,us_per_call,derived`` CSV rows (times already in the unit
named by each row's suffix: *_ms rows are milliseconds, *_bytes raw).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from . import kernels_bench, paper_tables

    suites = []
    for fn in paper_tables.ALL:
        suites.append((fn.__name__, fn))
    from . import scalability
    suites.append(("fig12_scalability", scalability.run))
    from . import response_time
    suites.append(("fig_response_time", response_time.run))
    from . import tenancy
    suites.append(("tenancy", tenancy.run))
    from . import device_enum
    suites.append(("fig_device_enum", device_enum.run))
    from . import ranked_enum
    suites.append(("fig_ranked_enum", ranked_enum.run))
    from . import streaming
    suites.append(("streaming", streaming.run))
    from . import sharing
    suites.append(("fig_sharing", sharing.run))
    suites.append(("kernels", kernels_bench.run))

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,-1,{e!r}")
            failures += 1
            continue
        for rname, val, derived in rows:
            print(f"{rname},{val},{derived}")
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
