"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a traced run wrote (``jax.profiler.
ProfileData``, nothing but JAX) into plain event tuples ``(name, start_ns,
dur_ns)``; everything after that is pure functions over those tuples, so
the arithmetic is tested without a chip on made-up events
(``bench/tests/test_bench_trace.py``).

The harness marks the host clock inside host spans named ``CLOCK_MARK``
while the profiler runs; their start times tie the trace's clock to the
host's (``to_host``).

Busy time is the union of the device's operation intervals inside the
traced interval; idle is the rest.  Each long idle gap is labelled with
the innermost host event over its midpoint (the shortest one that covers
it), which names what the host was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, dur_ns)

# lines of a TPU device plane that hold one event per executed operation;
# other lines (modules, steps) nest over these and would double count
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
# operations whose event spans the operations of their body: left out of
# per-operation time, which would count the body twice
CONTAINERS = ("while.", "conditional.", "call.")
CLOCK_MARK = "bench:clock"  # host span the harness opens at a known time


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load(path: str) -> dict:
    """``{"devices": {plane: {line: [Event]}}, "host": {line: [Event]}}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dest = out["devices"].setdefault(plane.name, {})
        elif plane.name.startswith("/host:CPU"):
            dest = out["host"]
        else:
            continue
        for line in plane.lines:
            evs = dest.setdefault(line.name, [])
            for e in line.events:
                evs.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return out


def device_planes(trace: dict, kind: str = "TPU") -> List[str]:
    """Device plane names of ``kind`` in device order."""
    names = [n for n in trace["devices"] if f"/device:{kind}:" in n]

    def index(n):
        tail = n.split(f"/device:{kind}:")[1]
        digits = "".join(ch for ch in tail if ch.isdigit())
        return int(digits) if digits else 0
    return sorted(names, key=index)


def op_events(plane: Dict[str, List[Event]]) -> List[Event]:
    for line in OP_LINES:
        if plane.get(line):
            return plane[line]
    for line in MODULE_LINES:
        if plane.get(line):
            return plane[line]
    return []


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def busy_ns(events: List[Event], t0: float, t1: float) -> float:
    iv = union(clip([(s, s + d) for _, s, d in events], t0, t1))
    return sum(e - s for s, e in iv)


def gaps(events: List[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of ``[t0, t1]`` between the events' union."""
    iv = union(clip([(s, s + d) for _, s, d in events], t0, t1))
    out, cur = [], t0
    for s, e in iv:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def short_name(name: str) -> str:
    """``%fusion.8 = bf16[16,2048]{...} fusion(...)`` (how a TPU trace
    names an operation) to ``fusion.8 bf16[16,2048]``."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}"[:120]


def op_totals(events: List[Event], t0: float, t1: float
              ) -> List[Tuple[str, float]]:
    """Device seconds per operation (``short_name``) inside the interval,
    largest first; control-flow containers are left out."""
    tot: Dict[str, float] = defaultdict(float)
    for name, s, d in events:
        lo, hi = max(s, t0), min(s + d, t1)
        short = short_name(name)
        if hi > lo and not short.startswith(CONTAINERS):
            tot[short] += hi - lo
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda kv: -kv[1])


def label(gap: Tuple[float, float], host: List[Event]) -> str:
    """The innermost host event over the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, d in host:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "(no host event)"


def host_events(trace: dict) -> List[Event]:
    return [e for evs in trace["host"].values() for e in evs if e[2] > 0]


def span(trace: dict) -> Tuple[float, float]:
    """The traced interval: from the first event of any plane to the last
    one's end."""
    lo, hi = float("inf"), float("-inf")
    for planes in (trace["host"], *trace["devices"].values()):
        for evs in planes.values():
            for _, s, d in evs:
                lo, hi = min(lo, s), max(hi, s + d)
    return lo, hi


def reduce(trace: dict, n_devices: int, *, top: int = 10) -> dict:
    """Busy, idle and per-operation figures of the first ``n_devices``
    device planes over the traced interval."""
    t0, t1 = span(trace)
    planes = device_planes(trace)[:n_devices]
    host = host_events(trace)
    per_dev = []
    for name in planes:
        evs = op_events(trace["devices"][name])
        idle = sorted(gaps(evs, t0, t1), key=lambda g: g[0] - g[1])[:top]
        per_dev.append({
            "plane": name,
            "busy_s": busy_ns(evs, t0, t1) / 1e9,
            "ops": op_totals(evs, t0, t1),
            "gaps": [(label(g, host), (g[1] - g[0]) / 1e9) for g in idle]})
    marks = sorted(s for n, s, _ in host if n == CLOCK_MARK)
    return {"t0_ns": t0, "t1_ns": t1, "window_s": (t1 - t0) / 1e9,
            "devices": per_dev, "marks_ns": marks}


def to_host(red: dict, host_marks) -> Optional[Tuple[float, float]]:
    """The traced interval on the host clock (seconds), from the first
    clock mark's trace time and the host time read inside it; None
    without a mark."""
    if not red or not red.get("marks_ns") or not host_marks \
            or host_marks[0] is None:
        return None
    off = red["marks_ns"][0] / 1e9 - host_marks[0]
    return red["t0_ns"] / 1e9 - off, red["t1_ns"] / 1e9 - off

