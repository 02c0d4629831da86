"""Reduce a JAX profiler trace (``*.xplane.pb``) of the window to the
numbers the benchmark reports.

``events`` reads the trace into plain lists: per device, its ops and its
XLA programs (modules) as ``(name, module, start_ns, end_ns)``; the host
threads' spans as ``(name, start_ns, end_ns)``; and the traced window.
``reduce`` turns those into

* ``busy_s``: the union of the intervals in which an op ran, averaged
  over the devices, and ``window_s``, the traced window;
* ``device_ops``: the 10 ops (by program and op name) that took most
  device time, with their seconds;
* ``module_s``: device seconds per XLA program;
* ``idle_gaps``: device idle time summed by what the host was doing
  while it lasted (the innermost host span over the gap's middle that
  is not one of the ``ignore`` spans), the 10 largest.
"""
from __future__ import annotations

import bisect
import collections
import heapq
import pathlib
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

TOP = 10
# host spans that cover nearly everything (a request in flight) and so
# say nothing about what the host was doing
IGNORE_SPANS = ("hcpe.submit",)


def events(path: str) -> Dict[str, object]:
    """The trace at ``path`` as plain lists (see the module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: List[Dict[str, list]] = []
    host: List[Tuple[str, int, int]] = []
    window_ns = 0
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            window_ns = int(st.get("profile_stop_time", 0)
                            - st.get("profile_start_time", 0))
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            lines = {line.name: line for line in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            if ops is None:
                continue
            modules = [(short(e.name), "", int(e.start_ns), int(e.end_ns))
                       for e in (mods.events if mods is not None else [])]
            devices.append({
                "ops": [(short(e.name), short(dict(e.stats).get(
                    "hlo_module", "")), int(e.start_ns), int(e.end_ns))
                        for e in ops.events],
                "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host, "window_ns": window_ns}


def short(name: str) -> str:
    """An op or program name without its HLO text or compile hash:
    ``%fusion.11 = s32[4] fusion(...)`` -> ``%fusion.11``,
    ``jit_f(1234)`` -> ``jit_f``."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted intervals covering the given ones."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _module_of(op: Tuple[str, str, int, int],
               modules: Sequence[Tuple[str, str, int, int]],
               starts: List[int]) -> str:
    """The program an op ran in: its own stat, else the module event
    that covers its start."""
    if op[1]:
        return op[1]
    i = bisect.bisect_right(starts, op[2]) - 1
    if i >= 0 and modules[i][3] >= op[2]:
        return modules[i][0]
    return "?"


def innermost(spans: Sequence[Tuple[str, int, int]], points: Sequence[int]
              ) -> List[str]:
    """For each point, the name of the shortest span that covers it
    ("no host span" where none does): one sweep over both, sorted."""
    spans = sorted(spans, key=lambda h: h[1])
    order = sorted(range(len(points)), key=lambda i: points[i])
    out = ["no host span"] * len(points)
    active: List[Tuple[int, int, int]] = []   # (duration, end, span index)
    nxt = 0
    for i in order:
        p = points[i]
        while nxt < len(spans) and spans[nxt][1] <= p:
            name, lo, hi = spans[nxt]
            heapq.heappush(active, (hi - lo, hi, nxt))
            nxt += 1
        while active and active[0][1] < p:
            heapq.heappop(active)
        if active:
            out[i] = spans[active[0][2]][0]
    return out


def reduce(ev: Dict[str, object], ignore: Sequence[str] = IGNORE_SPANS
           ) -> Dict[str, object]:
    """The reported numbers of one trace's ``events``."""
    devices = ev["devices"]
    window_ns = int(ev["window_ns"])  # type: ignore[arg-type]
    busy_ns = 0
    per_op: Dict[str, float] = collections.Counter()
    per_module: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for dev in devices:  # type: ignore[union-attr]
        modules = sorted(dev["modules"], key=lambda m: m[2])
        starts = [m[2] for m in modules]
        for m in modules:
            per_module[m[0]] += (m[3] - m[2]) / 1e9
        for op in dev["ops"]:
            per_op[f"{_module_of(op, modules, starts)}/{op[0]}"] += \
                (op[3] - op[2]) / 1e9
        busy = union((op[2], op[3]) for op in dev["ops"])
        busy_ns += sum(hi - lo for lo, hi in busy)
        edges = [0] + [x for iv in busy for x in iv] + [window_ns]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i])
    if not per_module:
        for name, secs in per_op.items():
            per_module[name.split("/", 1)[0]] += secs
    idle: Dict[str, float] = collections.Counter()
    for (lo, hi), label in zip(gaps, innermost(
            [h for h in ev["host"] if h[0] not in ignore],  # type: ignore
            [(lo + hi) // 2 for lo, hi in gaps])):
        idle[label] += (hi - lo) / 1e9
    n_dev = len(devices)  # type: ignore[arg-type]
    return {
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": window_ns / 1e9,
        "device_ops": [[k, v / n_dev] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "module_s": {k: v / n_dev for k, v in per_module.items()},
        "idle_gaps": [[k, v / n_dev] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def reduce_dir(trace_dir: pathlib.Path,
               ignore: Sequence[str] = IGNORE_SPANS
               ) -> Optional[Dict[str, object]]:
    """``reduce`` of the one trace the profiler wrote under
    ``trace_dir``; None where no device ran an op (a CPU run)."""
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"{len(found)} traces under {trace_dir}")
    ev = events(str(found[0]))
    if not any(dev["ops"] for dev in ev["devices"]):  # type: ignore
        return None
    return reduce(ev, ignore)
