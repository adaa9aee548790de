"""Window accounting and the metric readers on a made-up run record."""
import os

from bench import run, window

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def flow(kind, intended, walls, status="completed", in_window=True,
         prompt=100, sent=None):
    return {"kind": kind, "prompt": [1] * prompt, "max_new": len(walls),
            "intended": intended,
            "sent": intended if sent is None else sent,
            "in_window": in_window, "status": status,
            "out": [7] * len(walls), "walls": walls, "preempted": False}


def record():
    flows = [
        # pre-roll and post-roll flows are not the window's
        flow("reactive", 5.0, [5.1, 10.5, 10.6], in_window=False),
        flow("reactive", 31.0, [31.2, 31.3], in_window=False),
        # the window's reactive flows: 0.2 s and 0.4 s to first token
        flow("reactive", 12.0, [12.2, 12.3, 12.4], sent=12.01),
        flow("reactive", 14.0, [14.4, 14.6], sent=14.03),
        # one never finished: counts as the longest wait
        flow("reactive", 20.0, [], status="failed"),
        # proactive: 3 of its tokens lie in the window
        flow("proactive", 8.0, [9.0, 9.5, 10.0, 10.5, 11.0], in_window=False),
        flow("proactive", 25.0, [25.5], prompt=50),
    ]
    return {"flows": flows, "ws": 10.0, "we": 30.0, "seconds": 20.0,
            "setup_s": 42.0, "traffic": {"drain_limit_s": 60.0},
            "counters": {"start": {"decode_device_calls": 10,
                                   "host_syncs": 5, "fused_steps": 90,
                                   "aborted_steps": 0,
                                   "jit_compilations": 7},
                         "end": {"decode_device_calls": 13,
                                 "host_syncs": 9, "fused_steps": 120,
                                 "aborted_steps": 10,
                                 "jit_compilations": 7}},
            "memory": {"peak_bytes": 3, "bytes_limit": 4},
            "trace": None, "trace_host": None, "peaks": None}


def read(name, rec):
    return run.metric_reader(name, METRICS)(rec)


def test_only_window_flows_count():
    rec = record()
    assert len(window.window_flows(rec)) == 4
    assert len(window.window_flows(rec, "reactive")) == 3


def test_tokens_committed_in_window():
    rec = record()
    # proactive walls in [10, 30): 10.0, 10.5, 11.0, 25.5
    assert read("proactive_tokens_per_s", rec) == 4 / 20.0
    # every token in the window but a first: 10.5, 10.6, 12.3, 12.4,
    # 14.6, 10.0, 10.5, 11.0 over 3 decode calls
    assert read("tokens_per_decode_call", rec) == 8 / 3
    assert read("preempted_step_share", rec) == 25.0
    assert read("compiles_in_window", rec) == 0
    assert read("hbm_peak_share", rec) == 75.0
    assert read("setup_s", rec) == 42.0


def test_generator_lag_and_tpot():
    rec = record()
    # the window's sends: reactive 10 ms and 30 ms late, the failed one
    # and the agent's request on time
    assert abs(read("generator_lag_p95_ms", rec) - 30.0) < 1e-6
    # (12.4 - 12.2) / 2 = 100 ms, (14.6 - 14.4) / 1 = 200 ms, and the
    # failed flow at the longest the run allows
    assert abs(read("reactive_tpot_p50_ms", rec) - 200.0) < 1e-6
    rec["flows"] = [f for f in rec["flows"] if f["status"] != "failed"]
    assert abs(read("reactive_tpot_p50_ms", rec) - 100.0) < 1e-6


def test_trace_metrics_stay_silent_without_a_trace():
    rec = record()
    assert read("device_idle_share", rec) is None
    assert read("decode_attn_roofline", rec) is None


def test_decode_attn_roofline_counts_the_traced_interval():
    """Kernel time over the traced interval against the tokens committed
    in the same interval on the host clock, which the clock mark maps."""
    from bench import flops
    from bench.model import load_config, model_config
    rec = record()
    cfg = model_config(load_config("qwen2.5-32b-L8"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the trace runs 1000..1004 s of its clock; the mark at 1001 s was
    # opened at host time 11.0 s, so the interval is 10..14 s on the host
    rec.update(cfg=cfg, conf={"kv_dtype": "bf16"}, peaks=peaks,
               trace_host=[11.0, 13.0],
               trace={"t0_ns": 1000e9, "t1_ns": 1004e9, "window_s": 4.0,
                      "marks_ns": [1001e9, 1003e9],
                      "devices": [{"busy_s": 3.0, "gaps": [],
                                   "ops": [("decode_attention", 0.002),
                                           ("fusion.3 bf16[2]", 1.0)]}]})
    # decode tokens (not first) committed in [10, 14): 10.5, 10.6 of the
    # pre-roll flow (prompt 100, indices 1, 2), 12.3, 12.4 (1, 2), and
    # the agent's 10.0, 10.5, 11.0 (prompt 100, indices 2, 3, 4)
    ctxs = [101, 102, 101, 102, 102, 103, 104]
    want = sum(flops.decode_attn_bytes(cfg, c) for c in ctxs) / 819e9
    assert abs(read("decode_attn_roofline", rec) - 100 * want / 0.002) \
        < 1e-9
    rec["trace"]["marks_ns"] = []
    assert read("decode_attn_roofline", rec) is None
