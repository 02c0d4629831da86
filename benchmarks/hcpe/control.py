"""The control of the benchmark's correctness check.

The deployment guarantees exact answers over *simple* paths.  The
control puts the plain reference in the program's place with that one
guarantee broken: ``ControlEngine`` answers every query with the s-t
walks of at most k edges (a vertex other than s and t may repeat), the
step a faster enumerator that skips the visited-vertex test would take.
At k = 4 a walk repeats a vertex only as
s, a, b, a, t, so the control differs from the truth exactly on the
queries whose endpoints share a neighbour.
Its runs must come out not ``correct``; their compared numbers are the
upper readings that the limits sit below.

    python3 benchmarks/hcpe/control.py --workload ep.k4_hot \\
        --seeds 11,12,13 --seconds 20

prints one JSON line per seed with the numbers compared and ``correct``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from hcpe import gen, reference, run  # noqa: E402


def control_engine(settings: Dict[str, Any], csr: gen.Csr):
    """An ``engine_factory`` for ``run.run_cell``: a ``ControlEngine``
    over the benchmark's own copy of the graph."""
    from repro.core import (BatchItem, BatchOutput, BatchPathEnum,
                            BatchTiming, CacheStats, EnumResult, EnumStats,
                            Plan)

    class ControlEngine(BatchPathEnum):
        """Answers with walks, computed by the reference."""

        def __init__(self, **kw: Any) -> None:
            super().__init__(**kw)
            self.batches: List[Dict[str, Any]] = []
            self.memo: Dict[Tuple[int, int, int, bool], EnumResult] = {}

        def answer(self, s: int, t: int, k: int, count_only: bool
                   ) -> EnumResult:
            key = (s, t, k, count_only)
            if key not in self.memo:
                if count_only:
                    count = int(gen.walk_counts(csr, s, t, k).sum())
                    walks = np.zeros((0, k + 1), np.int32)
                else:
                    walks = reference.paths(csr, s, t, k, simple=False)
                    count = walks.shape[0]
                self.memo[key] = EnumResult(
                    paths=walks,
                    lengths=(walks >= 0).sum(axis=1).astype(np.int32) - 1,
                    count=count, stats=EnumStats(results=count))
            return self.memo[key]

        def run(self, graph, queries, count_only: bool = True, **_kw: Any):
            """The control's answers, shaped as the program's output."""
            t0 = time.perf_counter()
            plan = Plan(method="dfs", cut=None, preliminary=-1.0,
                        used_full_estimator=False)
            items = [BatchItem(s=s, t=t, k=k, result=self.answer(
                s, t, k, count_only), plan=plan,
                index_cached=False, deduplicated=False, latency_seconds=0.0)
                for s, t, k in queries]
            t1 = time.perf_counter()
            out = BatchOutput(items=items, timing=BatchTiming(
                enumerate_seconds=t1 - t0, total_seconds=t1 - t0,
                started_at=t0, ended_at=t1), cache_stats=CacheStats(),
                distinct_queries=len(set(queries)))
            self.batches.append(run.summarize(out))
            return out

    return ControlEngine(**settings)


def control_run(name: str, seed: int, seconds: float, **kw: Any
                ) -> Dict[str, Any]:
    """One run of cell ``name`` with the control in the program's
    place."""
    return run.run_cell(name, seed, seconds, False,
                        engine_factory=control_engine, **kw)


def main(argv: Optional[List[str]] = None) -> int:
    """Run the control of one cell on each of the given seeds."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(x) for x in args.seeds.split(",")):
        out = control_run(args.workload, seed, args.seconds,
                          log=lambda m: print(m, file=sys.stderr))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
