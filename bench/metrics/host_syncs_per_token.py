"""Device-to-host token fetches (``host_syncs``) per token committed in
the window."""
from bench.window import delta, walls_in


def read(rec):
    syncs = delta(rec, "host_syncs")
    n = len(walls_in(rec, rec["ws"], rec["we"]))
    if syncs is None or n == 0:
        return None
    return syncs / n
