"""Mean wait of the proactive flows that began prefill in the window, from
their submit to the launch of their first prefill chunk, ms: time a
closed-loop agent loses before the engine takes up its request."""
from bench.window import delta


def read(rec):
    wait = delta(rec, "queue_wait_s_proactive")
    n = delta(rec, "queue_waits_proactive")
    if wait is None or not n:
        return None
    return 1000.0 * wait / n
