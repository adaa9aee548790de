"""Share of the window the engine thread spent in host work of its own
(the program's phase clocks ``host_loop_s``, ``host_sched_s``,
``host_poll_s``, ``host_launch_s`` and ``host_commit_s``: event loop,
scheduler, arrival poll, program launches and token commits; not waiting
on the device, compiling or collecting garbage), %."""
from bench.window import delta

CLOCKS = ("host_loop_s", "host_sched_s", "host_poll_s", "host_launch_s",
          "host_commit_s")


def read(rec):
    parts = [delta(rec, k) for k in CLOCKS]
    if any(p is None for p in parts) or rec["we"] <= rec["ws"]:
        return None
    return 100.0 * sum(parts) / (rec["we"] - rec["ws"])
