"""frontier_roofline: the frontier kernels' share of their memory-bound
roofline, in percent.

Bytes are those the work needs at least: each index edge the expansion
reads (4 bytes) and each partial path it writes ((k + 1) int32 columns),
counted from the Fig.-6 ``EnumStats`` of the window's dfs-planned
queries, which are the same whichever driver ran them.  The time is the
device time of the frontier programs (``_frontier_expand_jit``,
``_frontier_fused_jit``, ``_deque_round_jit``) in the trace.  Their
operations are int32 compares and gathers, far under the chip's peak
rate, so bytes bound them."""

PROGRAMS = ("frontier", "deque")


def read(rec):
    """Least time at peak HBM bandwidth over measured kernel time, %."""
    tr, peak = rec["trace"], rec["peak"]
    if tr is None or peak is None:
        return None
    secs = sum(v for name, v in tr["module_s"].items()
               if any(p in name for p in PROGRAMS))
    k1 = rec["k"] + 1
    nbytes = sum(4 * it["edges_accessed"] + 4 * k1 * it["partials_generated"]
                 for b in rec["batches"] for it in b["items"]
                 if it["plan"] == "dfs")
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / secs
