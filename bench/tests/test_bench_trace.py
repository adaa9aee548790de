"""Trace reduction on made-up events: busy union, idle gaps, per-op time
and the host label of a gap."""
from bench import trace as tr


def test_union_merges_overlaps():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (8, 8)]) == \
        [(0, 3), (5, 7)]


def test_busy_and_gaps_inside_the_interval():
    evs = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0),
           ("d", 90.0, 20.0)]
    assert tr.busy_ns(evs, 0.0, 100.0) == 15 + 5 + 10
    assert tr.gaps(evs, 0.0, 100.0) == [(15.0, 30.0), (35.0, 90.0)]
    assert tr.gaps([], 0.0, 4.0) == [(0.0, 4.0)]


def test_op_totals_clip_to_interval_and_sort():
    evs = [("fusion", 0.0, 2e9), ("dot", 1e9, 1e9), ("fusion", 5e9, 1e9)]
    assert tr.op_totals(evs, 0.0, 5.5e9) == [("fusion", 2.5), ("dot", 1.0)]


def test_gap_label_is_innermost_host_event():
    host = [("outer", 0.0, 100.0), ("inner", 40.0, 20.0),
            ("elsewhere", 70.0, 5.0)]
    assert tr.label((45.0, 55.0), host) == "inner"
    assert tr.label((200.0, 210.0), host) == "(no host event)"


def test_reduce_reads_device_and_host_planes():
    trace = {"devices": {"/device:TPU:0": {
                 "XLA Modules": [("jit_fn", 0.0, 3e9)],
                 "XLA Ops": [("fusion.1", 0.0, 1e9), ("custom", 2e9, 1e9)]},
                         "/device:TPU:1": {"XLA Ops": [("x", 0.0, 4e9)]}},
             "host": {"python": [("bench:decode_iteration", 0.5e9, 2e9)]}}
    red = tr.reduce(trace, 1)
    assert red["window_s"] == 4.0
    dev = red["devices"][0]
    assert dev["plane"] == "/device:TPU:0" and dev["busy_s"] == 2.0
    assert dev["gaps"][0] == ("bench:decode_iteration", 1.0)
    assert len(tr.reduce(trace, 2)["devices"]) == 2


def test_short_name_of_a_tpu_operation():
    name = ("%fusion.136 = bf16[12,27648]{1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[8,5120,27648]{2,1,0} %get-tuple-element.670), kind=kOutput")
    assert tr.short_name(name) == "fusion.136 bf16[12,27648]"
    assert tr.short_name("decode_attention") == "decode_attention"


def test_loops_are_not_counted_over_their_bodies():
    evs = [("%while.6 = (s32[]) while(...)", 0.0, 4e9),
           ("%fusion.1 = bf16[2] fusion(...)", 1e9, 1e9)]
    assert tr.op_totals(evs, 0.0, 4e9) == [("fusion.1 bf16[2]", 1.0)]
    assert tr.busy_ns(evs, 0.0, 4e9) == 4e9


def test_clock_marks_map_the_trace_onto_the_host():
    """A mark opened at host time 100.0 s starts at trace time 7 s: the
    traced interval 6..11 s lies at 99..104 s on the host clock."""
    trace = {"devices": {"/device:TPU:0": {"XLA Ops": [("f", 6e9, 5e9)]}},
             "host": {"python": [(tr.CLOCK_MARK, 7e9, 1e3),
                                 (tr.CLOCK_MARK, 10e9, 1e3)]}}
    red = tr.reduce(trace, 1)
    assert red["marks_ns"] == [7e9, 10e9]
    lo, hi = tr.to_host(red, [100.0, 103.0])
    assert abs(lo - 99.0) < 1e-9 and abs(hi - 104.0) < 1e-6
    assert tr.to_host(dict(red, marks_ns=[]), [100.0, 103.0]) is None


def test_prefill_device_busy_share_reads_device_one():
    """Device 1's busy union over the traced interval: 0.2-0.6 s and
    2.0-2.1 s of 4 s; no value from a trace of one device."""
    import json
    import os

    from bench import run
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "traces", "two_plane.json")) as f:
        trace = json.load(f)
    read = run.metric_reader("prefill_device_busy_share",
                             os.path.join(run.BENCH_DIR, "metrics"))
    for n in (2, 4):
        red = tr.reduce(trace, n)
        assert red["window_s"] == 4.0
        assert abs(read({"trace": red}) - 12.5) < 1e-9
    assert read({"trace": tr.reduce(trace, 1)}) is None
    assert read({"trace": None}) is None
