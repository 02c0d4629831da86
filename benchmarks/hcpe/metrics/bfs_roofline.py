"""bfs_roofline: the stacked bounded BFS program's share of its
memory-bound roofline, in percent.

Bytes are those the work needs at least.  Each micro-batch with misses
runs the BFS in two directions (from each s, and to each t), each for
k hops, and a hop reads the m predecessor ids once (4 bytes each) and
reads and writes the distance rows of the batch's misses (4 bytes each
way per vertex and row):

    bytes = sum over batches of 2 * k * (4 * m + 8 * misses * n)

n and m are the cell's graph (the configuration's ``graph`` and
``graph_at_seed``, found through ``record["cell"]`` and
``BENCHMARK.json``).  The time is the device time of the programs named
``stacked_bfs`` in the trace.  Its operations are int32 compares, adds
and gathers, far under the chip's peak rate, so bytes bound it."""
import json
import pathlib

PROGRAM = "stacked_bfs"
ROOT = pathlib.Path(__file__).resolve().parents[3]


def graph_size(cell):
    """(n, m) of the graph of workload ``cell``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = {w["name"]: w["config"] for w in bench["workloads"]}[cell]
    path = {c["name"]: c["file"] for c in bench["configs"]}[config]
    cfg = json.loads((ROOT / path).read_text())
    return int(cfg["graph"]["n"]), int(cfg["graph_at_seed"]["edges"])


def read(rec):
    """Least time at peak HBM bandwidth over measured BFS time, %."""
    tr, peak = rec["trace"], rec["peak"]
    if tr is None or peak is None:
        return None
    secs = sum(v for name, v in tr["module_s"].items() if PROGRAM in name)
    if secs <= 0:
        return None
    n, m = graph_size(rec["cell"])
    nbytes = sum(2 * rec["k"] * (4 * m + 8 * b["misses"] * n)
                 for b in rec["batches"] if b["misses"])
    if nbytes <= 0:
        return None
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / secs
