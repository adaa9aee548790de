"""What the metric readers share: a run record's flows and counters over
the measured window.

A run record (``bench/run.py``) holds ``flows`` (each with ``kind``,
``intended`` and ``sent`` host times, ``in_window``, ``status``, ``out``
tokens and their host ``walls``), the window ``ws``..``we``, the backend's
counters at its start and end (``counters``), and, for a traced run, the
reduced trace (``trace``) and the host times the profiler ran
(``trace_host``).
"""
from __future__ import annotations

from typing import List


def window_flows(rec: dict, kind: str = None) -> List[dict]:
    return [f for f in rec["flows"] if f["in_window"]
            and (kind is None or f["kind"] == kind)]


def walls_in(rec: dict, t0: float, t1: float, kind: str = None, *,
             first: bool = True) -> List[tuple]:
    """(flow, token index) of every token committed in ``[t0, t1)``;
    ``first=False`` leaves out each flow's first token, which prefill
    makes."""
    out = []
    for f in rec["flows"]:
        if kind is not None and f["kind"] != kind:
            continue
        for i, w in enumerate(f["walls"]):
            if t0 <= w < t1 and (first or i > 0):
                out.append((f, i))
    return out


def delta(rec: dict, key: str):
    """A backend counter's growth over the window (None if unread)."""
    c = rec["counters"]
    if "start" not in c or "end" not in c or key not in c["start"]:
        return None
    return c["end"][key] - c["start"][key]
