"""Median over every reactive flow sent in the window of (last token - first
token) / (tokens - 1): the streaming speed its user sees (host clock).  A
flow that did not complete counts as the longest the run allows."""
from bench.loadgen import percentile
from bench.window import window_flows


def read(rec):
    worst = (rec["seconds"] + rec["traffic"]["drain_limit_s"]) * 1e3
    vals = []
    for f in window_flows(rec, "reactive"):
        w = f["walls"]
        if f["status"] != "completed":
            vals.append(worst)
        elif len(w) >= 2:
            vals.append((w[-1] - w[0]) / (len(w) - 1) * 1e3)
    return percentile(vals, 50)
