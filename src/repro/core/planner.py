"""Cost-based query optimizer (Section 6 / Figure 2).

Two-phase, exactly as the paper:
  1. preliminary estimator (Eq. 5, O(k²)) — if T̂ ≤ τ, go straight to
     IDX-DFS (short queries mustn't pay optimization overhead);
  2. otherwise run the full-fledged DP (Alg. 5), find the cut i*, compare
     T_DFS = Σ|Q[0:i]| against T_JOIN = |Q| + … (§6.3), pick the cheaper.

τ defaults to 1e5, the value the paper calibrates in §6.2 (time to find 1e5
results ≈ optimization time on their workloads); ``calibrate_tau`` re-runs
the paper's calibration procedure on this machine.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

from .. import trace
from . import estimator as est
from .index import LightweightIndex
from .join import hop_count_dp

DEFAULT_TAU = 1e5


@dataclasses.dataclass
class Plan:
    method: str                 # "dfs" | "join"
    cut: Optional[int]          # i* when method == "join"
    preliminary: float          # T̂ from Eq. 5
    used_full_estimator: bool
    t_dfs: Optional[float] = None
    t_join: Optional[float] = None
    est_results: Optional[float] = None
    dp: Optional[est.WalkCountDP] = None
    optimize_seconds: float = 0.0


def plan_query(index: LightweightIndex, tau: float = DEFAULT_TAU,
               backend: Optional[str] = None) -> Plan:
    """Two-phase plan for one query.  ``backend`` (host|device|auto, §9)
    picks where the full-fledged DP runs when the τ gate trips — the
    device leg is the semiring-kernel build of join.hop_count_dp, which
    is bit-identical to the host build (it promotes itself to the host
    on f32 overflow), so the *plan* never depends on the backend, only
    the derivation cost does.  The O(k²) preliminary estimate is host
    scalar math always.  ``optimize_seconds`` is the ``pathenum.plan``
    span's duration."""
    with trace.span("pathenum.plan") as sp:
        t_hat = est.preliminary_estimate(index)
        dp = hop_count_dp(index, backend) if t_hat > tau else None
    if dp is None:
        return Plan(method="dfs", cut=None, preliminary=t_hat,
                    used_full_estimator=False, optimize_seconds=sp.seconds)
    # a cut at the boundary degenerates to the left-deep plan
    cut = dp.cut
    if cut <= 0 or cut >= index.k or dp.t_dfs <= dp.t_join:
        return Plan(method="dfs", cut=None, preliminary=t_hat,
                    used_full_estimator=True, t_dfs=dp.t_dfs,
                    t_join=dp.t_join, est_results=dp.q_total, dp=dp,
                    optimize_seconds=sp.seconds)
    return Plan(method="join", cut=cut, preliminary=t_hat,
                used_full_estimator=True, t_dfs=dp.t_dfs, t_join=dp.t_join,
                est_results=dp.q_total, dp=dp, optimize_seconds=sp.seconds)


def calibrate_tau(graph, queries, k: int = 6, start: float = 10.0,
                  limit: float = 1e7) -> float:
    """The paper's τ calibration (§6.2): grow τ by 10× until the time to find
    τ results exceeds the join-plan optimization time for most queries."""
    from .index import build_index
    from .enumerate import enumerate_paths_idx

    tau = start
    while tau < limit:
        slower = 0
        for (s, t) in queries:
            idx = build_index(graph, s, t, k)
            t0 = time.perf_counter()
            est.walk_count_dp(idx)
            opt_time = time.perf_counter() - t0
            t0 = time.perf_counter()
            try:
                enumerate_paths_idx(idx, first_n=int(tau), count_only=False)
            except Exception:
                pass
            enum_time = time.perf_counter() - t0
            if enum_time > opt_time:
                slower += 1
        if slower >= len(queries) * 0.5:
            return tau
        tau *= 10
    return tau
