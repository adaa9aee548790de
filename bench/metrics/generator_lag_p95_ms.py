"""p95 over the window's flows of how late the load generator sent each
one (host clock): a reactive flow after its scheduled time, an agent's
next request after its previous reply's last token.  A starved generator
would otherwise read as a slow server, or a fast one."""
from bench.loadgen import percentile
from bench.window import window_flows


def read(rec):
    return percentile([(f["sent"] - f["intended"]) * 1e3
                       for f in window_flows(rec)], 95)
