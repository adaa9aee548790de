"""The readers of the program's phase clocks and tracing counters, on
made-up run records: what each computes over the window, and that each is
silent where the program has no such counter (a parent commit without
in-program tracing)."""
import os

from bench import run

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
CLOCKS = ("host_loop_s", "host_sched_s", "host_poll_s", "host_launch_s",
          "host_compile_s", "host_fetch_s", "host_commit_s", "host_gc_s")


def record(start: dict, end: dict) -> dict:
    return {"flows": [], "ws": 10.0, "we": 30.0, "seconds": 20.0,
            "counters": {"start": start, "end": end}, "trace": None}


def counters(scale: float) -> dict:
    out = {k: scale * (i + 1) for i, k in enumerate(CLOCKS)}
    out.update(decode_kv_positions=int(1000 * scale),
               decode_kv_positions_live=int(250 * scale),
               queue_wait_s_proactive=0.5 * scale,
               queue_waits_proactive=int(4 * scale),
               host_syncs=7)
    return out


def read(name, rec):
    return run.metric_reader(name, METRICS)(rec)


def test_engine_host_share_sums_the_host_clocks_over_the_window():
    rec = record(counters(1.0), counters(2.0))
    # loop + sched + poll + launch + commit grew by 1 + 2 + 3 + 4 + 7 s;
    # compile, fetch and gc are left out; the window is 20 s
    assert abs(read("engine_host_share", rec) - 100 * 17 / 20) < 1e-9


def test_decode_kv_fill_and_proactive_queue_wait():
    rec = record(counters(1.0), counters(3.0))
    # 500 live of 2000 positions moved in the window
    assert read("decode_kv_fill", rec) == 25.0
    # 1.0 s over 8 flows that began prefill in the window
    assert abs(read("proactive_queue_wait_ms", rec) - 125.0) < 1e-9


def test_silent_without_work_in_the_window():
    c = counters(1.0)
    rec = record(c, dict(c))
    assert read("decode_kv_fill", rec) is None
    assert read("proactive_queue_wait_ms", rec) is None
    assert read("engine_host_share", rec) == 0.0


def test_silent_where_the_program_has_no_such_counter():
    old = {"host_syncs": 1, "decode_device_calls": 2}
    rec = record(old, dict(old))
    for name in ("engine_host_share", "decode_kv_fill",
                 "proactive_queue_wait_ms"):
        assert read(name, rec) is None, name
    assert read("engine_host_share", {"counters": {}, "ws": 0.0,
                                      "we": 1.0}) is None
