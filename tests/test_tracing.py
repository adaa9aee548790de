"""In-program tracing (core/tracing.py): exclusive phase clocks, the spans
they open in a profiler trace, the KV-position and queue-wait counters,
named device programs, and the launcher's report of them."""
import gc
import os
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import tracing  # noqa: E402
from repro.core.backend import JaxRealBackend, SimBackend  # noqa: E402
from repro.core.requests import Priority, Request  # noqa: E402
from repro.launch.frontend import ServingFrontend  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COUNTERS = ("decode_kv_positions", "decode_kv_positions_live",
            "decode_kv_bytes_written",
            "queue_wait_s_reactive", "queue_wait_s_proactive",
            "queue_waits_reactive", "queue_waits_proactive")


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_tiny_config
    from repro.models import init_params
    cfg = get_tiny_config("llama3-405b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def engine(tiny):
    from repro.core.engine import RealAgentXPUEngine
    cfg, params = tiny
    return RealAgentXPUEngine(cfg, params, max_len=128, max_fused_steps=8,
                              decode_segment_steps=2)


def _prompt(cfg, seed, plen):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                                (1, plen))


def _serve(fe, cfg, n, seed=0):
    handles = [fe.submit(_prompt(cfg, seed + i, 10 + i),
                         priority=Priority.REACTIVE if i % 3 == 0
                         else Priority.PROACTIVE, max_new_tokens=6 + i)
               for i in range(n)]
    for h in handles:
        assert h.result(timeout=300)["status"] == "completed"
    return handles


class _Clock:
    """A ``perf_counter`` stand-in that the test moves."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def test_nested_phase_pauses_its_parent(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(tracing, "perf_counter", clock)
    ph = tracing.Phases()
    with ph("engine.turn", "host_loop_s"):
        clock.now = 1.0
        with ph("sched.complete", "host_sched_s", rid=3):
            clock.now = 2.0
            with ph("backend.launch", "host_launch_s", kind="decode"):
                clock.now = 5.0
            clock.now = 5.5
        clock.now = 7.0
    assert ph.seconds["host_loop_s"] == 1.0 + 1.5
    assert ph.seconds["host_sched_s"] == 1.0 + 0.5
    assert ph.seconds["host_launch_s"] == 3.0
    # the clocks add up to the outermost phase's wall time
    assert ph.total(tracing.PHASE_KEYS) == 7.0
    # a phase that raises still closes, and the exception goes on
    with pytest.raises(KeyError):
        with ph("backend.fetch", "host_fetch_s"):
            clock.now = 8.0
            raise KeyError
    assert ph.seconds["host_fetch_s"] == 1.0


def test_every_clock_and_counter_exists_before_any_work(tiny):
    cfg, params = tiny
    be = JaxRealBackend(cfg, params, pool_slots=2, max_len=64)
    st = be.stats()
    for key in tracing.PHASE_KEYS + COUNTERS:
        assert st[key] == 0, key
    assert st[tracing.GC_KEY] >= 0  # a collection may already have run
    # the simulation-only backend has neither spans nor clocks
    sim = SimBackend()
    assert sim.phases is tracing.NO_PHASES
    assert not any(k.startswith("host_") for k in sim.stats())


def test_gc_is_timed_apart(tiny):
    cfg, params = tiny
    be = JaxRealBackend(cfg, params, pool_slots=2, max_len=64)
    before = be.phases.total(tracing.PHASE_KEYS)
    gc.collect()
    assert be.stats()["host_gc_s"] > 0
    assert be.phases.total(tracing.PHASE_KEYS) == before


def test_phase_clocks_cover_the_engine_thread(engine, tiny):
    """Over a front-end run, the engine thread's phase seconds add up to
    its wall time inside ``engine.run`` (within 5%)."""
    cfg, _ = tiny
    wall = [0.0]
    run = engine.run

    def timed(*a, **kw):
        t = time.perf_counter()
        try:
            return run(*a, **kw)
        finally:
            wall[0] += time.perf_counter() - t
    before = engine.backend.phases.total(tracing.PHASE_KEYS)
    engine.run = timed
    try:
        with ServingFrontend(engine) as fe:
            _serve(fe, cfg, 6)
    finally:
        del engine.run
    phases = engine.backend.phases.total(tracing.PHASE_KEYS) - before
    assert wall[0] > 0
    assert abs(phases - wall[0]) <= 0.05 * wall[0], (phases, wall[0])


def test_kv_position_counters_match_a_hand_count(tiny):
    """Two flows at positions 5 and 9 in a fused run of 6 steps in
    segments of 2 rows x 16 KV positions; the third segment is cancelled
    unlaunched and counts on neither side."""
    cfg, params = tiny
    be = JaxRealBackend(cfg, params, pool_slots=2, max_len=64,
                        decode_segment_steps=2)
    reqs = [Request(id=i, priority=Priority.PROACTIVE, prompt_len=n,
                    max_new_tokens=8, arrival_time=0.0,
                    tokens=_prompt(cfg, 40 + i, n))
            for i, n in enumerate((5, 9))]
    for r in reqs:
        be.register(r)
        be.prefill_chunk(r, 0, r.prompt_len, 0.0)
        be.prefill_done(r, 0.0)
    be.decode_run(reqs, 6, 0.0)  # segment 1: positions 5, 9 -> 7, 11
    for _ in range(3):  # the third replay launches segment 2 -> 9, 13
        be.decode_iteration(reqs, 0.0)
    be.request_preempt(0.0)  # segment 3 never launches
    be.decode_iteration(reqs, 0.0)  # segment 2's buffered row
    st = be.stats()
    assert st["aborted_steps"] == 2 and st["fused_steps"] == 4
    # each step moves rows x kv_limit = 2 x 16 positions
    assert st["decode_kv_positions"] == 2 * (2 * 2 * 16)
    # live: (6 + 7) + (10 + 11) in segment 1, (8 + 9) + (12 + 13) in 2
    assert st["decode_kv_positions_live"] == 34 + 42
    assert st["decode_kv_positions_live"] <= st["decode_kv_positions"]


def test_kv_bytes_written_match_a_hand_count(tiny):
    """Three flows in slots 0-2 (a row bound of 4): a fused run of 6 steps
    in segments of 2 launches two segments, the third is cancelled
    unlaunched and counts nothing, then one single-step decode follows.
    Each launched step writes one position's K and V of every layer, and
    its ``slot_pos``, for each of the three active rows, not the four
    computed ones."""
    cfg, params = tiny
    be = JaxRealBackend(cfg, params, pool_slots=4, max_len=64,
                        decode_segment_steps=2)
    reqs = [Request(id=i, priority=Priority.PROACTIVE, prompt_len=n,
                    max_new_tokens=12, arrival_time=0.0,
                    tokens=_prompt(cfg, 70 + i, n))
            for i, n in enumerate((5, 9, 7))]
    for r in reqs:
        be.register(r)
        be.prefill_chunk(r, 0, r.prompt_len, 0.0)
        be.prefill_done(r, 0.0)
    be.decode_run(reqs, 6, 0.0)  # segment 1 launches
    for _ in range(3):  # the third replay launches segment 2
        be.decode_iteration(reqs, 0.0)
    be.request_preempt(0.0)  # segment 3 never launches
    be.decode_iteration(reqs, 0.0)  # segment 2's buffered row
    assert be.stats()["decode_rows"] == 4
    be.decode_iteration(reqs, 0.0)  # one single-step program
    st = be.stats()
    assert st["aborted_steps"] == 2 and st["decode_device_calls"] == 3
    item = np.dtype(be.dtype).itemsize
    token = cfg.num_layers * (2 * cfg.num_kv_heads * cfg.head_dim * item + 4)
    assert st["decode_kv_bytes_written"] == (2 + 2 + 1) * 3 * token


def test_queue_waits_count_flows_that_began_prefill(engine, tiny):
    cfg, _ = tiny
    st0 = engine.stats()
    reqs = [Request(id=1000 + i, priority=p, prompt_len=12,
                    max_new_tokens=4, arrival_time=0.0,
                    tokens=_prompt(cfg, 60 + i, 12))
            for i, p in enumerate([Priority.REACTIVE] * 2
                                  + [Priority.PROACTIVE] * 3)]
    for r in reqs:
        engine.submit(r)
    # cancelled before the run: never begins prefill, never counted
    assert engine.cancel(reqs[-1])
    engine.run()
    st = engine.stats()
    d = {k: st[k] - st0[k] for k in COUNTERS}
    assert d["queue_waits_reactive"] == 2
    assert d["queue_waits_proactive"] == 2
    assert d["queue_wait_s_reactive"] > 0 and d["queue_wait_s_proactive"] > 0
    assert not engine.backend._waiting


def test_spans_land_in_a_profiler_trace(engine, tiny, tmp_path):
    """A CPU profiler trace of a front-end run, read as the benchmark
    reads it, holds the program's own spans."""
    sys.path.insert(0, ROOT)
    try:
        from bench import trace
    finally:
        sys.path.remove(ROOT)
    cfg, _ = tiny
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with ServingFrontend(engine) as fe:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(fe, cfg, 4, seed=80)
        finally:
            jax.profiler.stop_trace()
    names = {e[0] for e in trace.host_events(
        trace.load(trace.find_xplane(str(tmp_path))))}
    for span in ("engine.turn", "sched.turn", "sched.arrival",
                 "sched.complete", "sched.dispatch", "frontend.arrivals",
                 "backend.launch", "backend.fetch", "backend.commit"):
        assert span in names, span


@pytest.mark.parametrize("kind", ["decode", "decode_run", "mask", "clear"])
def test_programs_are_named_after_their_kind(tiny, kind):
    """``jit_<kind>``, not ``jit_fn``, in the profiler's XLA Modules."""
    cfg, params = tiny
    be = JaxRealBackend(cfg, params, pool_slots=2, max_len=64)
    pool, toks, mask = be._pool, be._toks, be._mask
    fn, args = {
        "decode": (be._decode_fn(2), (params, pool, toks, mask)),
        "decode_run": (be._decode_run_fn(2, 2), (params, pool, toks, mask)),
        "mask": (be._mask_update_fn(2, 1),
                 (mask, jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))),
        "clear": (be._clear_fn(2), (toks, mask, jnp.int32(0))),
    }[kind]
    assert f"module @jit_{kind} " in fn.lower(*args).as_text()


def test_launcher_reports_phases_waits_and_kv_fill(capsys, monkeypatch):
    from repro.launch import compile_cache, serve
    # tests never turn on the persistent compilation cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache",
                        lambda: None)
    serve.main(["--real", "--horizon", "6", "--rate", "1.0",
                "--reactive-interval", "3"])
    out = capsys.readouterr().out
    assert "[real] decode KV fill: " in out
    assert " KV bytes written in place" in out
    assert "[real] queue wait, submit to first prefill launch: " in out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("[real] engine thread, self seconds"))
    for word in ("loop", "sched", "poll", "launch", "compile", "fetch",
                 "commit", "gc"):
        assert f" {word} " in line, word
