"""Abortable fused decode, slack-aware piggybacking, and streaming
arrivals in real mode (DESIGN.md §8): reactive arrival mid-fused-run aborts
at a segment boundary with token-exact replay, preempted proactive decode
resumes with no KV corruption on the donated pool, piggybacked proactive
steps match serialized execution, mid-run ``submit`` works, and a released
mid-prefill slot can neither double-free nor rebind stale."""
import copy

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import AgentXPUEngine, Priority, Request
from repro.core.annotation import INTEL_CORE_ULTRA_5_125H
from repro.core.engine import make_scheduler
from repro.core.heg import HEG


def _mk_requests(cfg, rng, arrivals, prompt_lens, out_tokens, reactive=()):
    reqs = []
    for i, (t, plen) in enumerate(zip(arrivals, prompt_lens)):
        reqs.append(Request(
            id=i,
            priority=Priority.REACTIVE if i in reactive
            else Priority.PROACTIVE,
            prompt_len=plen, max_new_tokens=out_tokens, arrival_time=t,
            tokens=rng.integers(0, cfg.vocab_size, (1, plen))))
    return reqs


def _reference_tokens(cfg, params, prompt, n_out, max_len):
    import jax.numpy as jnp
    from repro.models import extend, prefill
    lg, cache = prefill(cfg, params, jnp.asarray(prompt), max_len=max_len,
                        dtype=jnp.float32)
    out = [int(lg.argmax(-1)[0])]
    for _ in range(n_out - 1):
        lg, cache = extend(cfg, params, cache,
                           jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(lg.argmax(-1)[0]))
    return out


def _tiny_real_engine(**kw):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_tiny_config
    from repro.core.engine import RealAgentXPUEngine
    from repro.models import init_params
    cfg = get_tiny_config("llama3-405b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params, RealAgentXPUEngine(cfg, params, max_len=128, **kw)


def _mid_decode_time(cfg, reqs, frac=0.4, **sched_kw):
    """Sim time inside the decode phase of a trace (same policy the real
    engine runs, so a reactive arrival at this instant lands mid-plan)."""
    eng = AgentXPUEngine(cfg, **sched_kw)
    eng.run_trace(copy.deepcopy(reqs))
    steps = [t for kind, _, t in eng.last_trace if kind == "decode_step"]
    assert steps, "trace has no decode phase"
    return steps[int(len(steps) * frac)]


# -- scheduler-side truncation arithmetic (no JAX) ---------------------------
def test_abort_truncates_at_segment_boundary():
    """_abort_fused_plan cuts the plan exactly at the backend's lazy
    segment-launch boundary: seg * ceil(max(committed, 1) / seg)."""
    heg = HEG(get_config("llama3.2-3b"), INTEL_CORE_ULTRA_5_125H)
    sched = make_scheduler("agent.xpu", heg, decode_segment_steps=8)
    cases = [
        # (total, committed) -> expected left after abort
        (32, 0, 8),    # announce launched segment 1 eagerly
        (32, 3, 5),    # mid segment 1
        (32, 8, 0),    # exactly at a boundary: nothing executed-but-unseen
        (32, 9, 7),    # segment 2 launched when the buffer drained
        (6, 2, 4),     # short plan: already fully launched -> no-op
    ]
    for total, committed, want_left in cases:
        sched._fused_plan = {"order": (1, 2), "left": total - committed,
                             "total": total}
        sched._abort_fused_plan(0.0)
        got = 0 if sched._fused_plan is None else sched._fused_plan["left"]
        assert got == want_left, (total, committed, got, want_left)
    # abortable_runs=False: the plan is never truncated
    sched2 = make_scheduler("agent.xpu", heg, abortable_runs=False)
    sched2._fused_plan = {"order": (1,), "left": 30, "total": 32}
    sched2._abort_fused_plan(0.0)
    assert sched2._fused_plan["left"] == 30


# -- reactive arrival mid-fused-run ------------------------------------------
def test_reactive_abort_mid_run_token_exact():
    """A reactive arriving mid-fused-run cancels the unlaunched segments
    (aborted_runs > 0), the already-produced block replays token-exactly,
    and every preempted proactive resumes on the donated pool with no KV
    corruption — outputs match both the unscheduled reference and a
    non-abortable run of the same trace."""
    cfg, params, eng = _tiny_real_engine(decode_segment_steps=2)
    _, _, eng_base = _tiny_real_engine(abortable_runs=False)
    rng = np.random.default_rng(41)
    n, out = 3, 24
    pro = _mk_requests(cfg, rng, [0.0] * n, [12, 14, 16], out)
    t_mid = _mid_decode_time(cfg, pro, frac=0.3, decode_segment_steps=2)
    reactive = Request(
        id=50, priority=Priority.REACTIVE, prompt_len=12, max_new_tokens=6,
        arrival_time=t_mid, tokens=rng.integers(0, cfg.vocab_size, (1, 12)))
    reqs = pro + [reactive]
    eng.serve(copy.deepcopy(reqs))
    eng_base.serve(copy.deepcopy(reqs))
    st = eng.stats()
    assert st["aborted_runs"] > 0  # a plan really was cut mid-flight
    assert st["aborted_steps"] > 0
    assert eng_base.stats()["aborted_runs"] == 0
    for r in pro:
        ref = _reference_tokens(cfg, params, r.tokens, out, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"
        assert eng_base.output_tokens(r.id) == ref, f"req {r.id}"
    ref = _reference_tokens(cfg, params, reactive.tokens, 6, 128)
    assert eng.output_tokens(50) == ref
    assert eng_base.output_tokens(50) == ref


def test_reactive_abort_token_exact_dual_device():
    """DESIGN.md §14: the dual-device engine — explicitly co-located when
    only one device is visible — preserves the §8 mid-run abort
    exactness unchanged (staged prefill and KV handoff are backend-local,
    so a reactive arriving mid-fused-run still truncates the plan and
    every flow replays token-exactly)."""
    import jax
    devs = jax.devices()
    cfg, params, eng = _tiny_real_engine(
        decode_segment_steps=2, dual_device=True,
        prefill_device=devs[1] if len(devs) >= 2 else devs[0])
    rng = np.random.default_rng(41)
    n, out = 3, 24
    pro = _mk_requests(cfg, rng, [0.0] * n, [12, 14, 16], out)
    t_mid = _mid_decode_time(cfg, pro, frac=0.3, decode_segment_steps=2)
    reactive = Request(
        id=50, priority=Priority.REACTIVE, prompt_len=12, max_new_tokens=6,
        arrival_time=t_mid, tokens=rng.integers(0, cfg.vocab_size, (1, 12)))
    eng.serve(copy.deepcopy(pro + [reactive]))
    assert eng.stats()["aborted_runs"] > 0
    assert eng.backend.validate() == []
    for r in pro:
        ref = _reference_tokens(cfg, params, r.tokens, out, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"
    assert eng.output_tokens(50) == _reference_tokens(
        cfg, params, reactive.tokens, 6, 128)


def test_sim_and_real_traces_identical_with_aborts():
    """Plan truncation is scheduler arithmetic, not backend behaviour: the
    kernel-completion trace of a sim run and a real run stays identical
    when a reactive abort fires mid-plan."""
    cfg, params, eng_real = _tiny_real_engine(decode_segment_steps=2)
    rng = np.random.default_rng(43)
    pro = _mk_requests(cfg, rng, [0.0, 0.0], [14, 12], 16)
    t_mid = _mid_decode_time(cfg, pro, frac=0.4, decode_segment_steps=2)
    reqs = pro + [Request(
        id=9, priority=Priority.REACTIVE, prompt_len=10, max_new_tokens=4,
        arrival_time=t_mid, tokens=rng.integers(0, cfg.vocab_size, (1, 10)))]
    eng_sim = AgentXPUEngine(cfg, decode_segment_steps=2)
    m_sim = eng_sim.run_trace(copy.deepcopy(reqs))
    m_real = eng_real.serve(copy.deepcopy(reqs))
    assert eng_real.stats()["aborted_runs"] > 0
    assert eng_sim.last_trace == eng_real.last_trace
    assert m_sim.sim_time == m_real.sim_time


# -- slack-aware piggybacking ------------------------------------------------
def test_piggyback_matches_serialized_execution():
    """Proactive decode steps piggybacked (fused) into a reactive prefill's
    slack produce exactly the tokens of serialized per-step execution."""
    cfg, params, eng = _tiny_real_engine(decode_segment_steps=2)
    _, _, eng_serial = _tiny_real_engine(max_fused_steps=1)
    rng = np.random.default_rng(47)
    n, out = 3, 32
    pro = _mk_requests(cfg, rng, [0.0] * n, [12, 14, 16], out)
    t_mid = _mid_decode_time(cfg, pro, frac=0.2, decode_segment_steps=2)
    # a LONG reactive prefill: many decode iterations fit in its slack
    reactive = Request(
        id=60, priority=Priority.REACTIVE, prompt_len=96, max_new_tokens=4,
        arrival_time=t_mid, tokens=rng.integers(0, cfg.vocab_size, (1, 96)))
    reqs = pro + [reactive]
    eng.serve(copy.deepcopy(reqs))
    eng_serial.serve(copy.deepcopy(reqs))
    assert eng.last_sched.piggyback_runs > 0  # fused under a live prefill
    assert eng.last_sched.piggyback_steps > 1
    for r in reqs:
        assert eng.output_tokens(r.id) == eng_serial.output_tokens(r.id), \
            f"req {r.id}"
    ref = _reference_tokens(cfg, params, reactive.tokens, 4, 128)
    assert eng.output_tokens(60) == ref


# -- streaming arrivals ------------------------------------------------------
def test_submit_mid_run_from_callback():
    """engine.submit() during an active run injects the request into the
    live event loop; it completes in the same run, token-exactly."""
    cfg, params, eng = _tiny_real_engine(decode_segment_steps=2)
    rng = np.random.default_rng(53)
    pro = _mk_requests(cfg, rng, [0.0, 0.0], [14, 12], 12)
    reactive = Request(
        id=70, priority=Priority.REACTIVE, prompt_len=10, max_new_tokens=4,
        arrival_time=0.0, tokens=rng.integers(0, cfg.vocab_size, (1, 10)))
    state = {"injected": False, "seen": 0}

    def on_token(req, tok):
        state["seen"] += 1
        if not state["injected"] and req.priority == Priority.PROACTIVE \
                and state["seen"] >= 6:
            state["injected"] = True
            assert eng._sim is not None  # genuinely mid-run
            eng.submit(copy.deepcopy(reactive))

    for r in pro:
        eng.submit(r, on_token=on_token)
    m = eng.run()
    assert state["injected"]
    assert {r.id for r in m.completed} == {0, 1, 70}
    done = {r.id: r for r in m.completed}
    assert done[70].arrival_time > 0.0  # stamped at the injection instant
    for r in pro:
        ref = _reference_tokens(cfg, params, r.tokens, 12, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"
    ref = _reference_tokens(cfg, params, reactive.tokens, 4, 128)
    assert eng.output_tokens(70) == ref


def test_arrival_source_polled_each_turn():
    """set_arrival_source: requests surface at the sim instant the source
    releases them, and the source is detachable."""
    cfg, params, eng = _tiny_real_engine()
    rng = np.random.default_rng(59)
    pro = _mk_requests(cfg, rng, [0.0], [16], 12)
    t_mid = _mid_decode_time(cfg, pro, frac=0.5)
    reactive = Request(
        id=80, priority=Priority.REACTIVE, prompt_len=10, max_new_tokens=4,
        arrival_time=0.0, tokens=rng.integers(0, cfg.vocab_size, (1, 10)))
    fired = []

    def source(now):
        if not fired and now >= t_mid:
            fired.append(now)
            return [reactive]
        return []

    eng.set_arrival_source(source)
    m = eng.serve(copy.deepcopy(pro))
    eng.set_arrival_source(None)
    assert fired and len(m.completed) == 2
    done = {r.id: r for r in m.completed}
    assert done[80].arrival_time >= t_mid
    ref = _reference_tokens(cfg, params, reactive.tokens, 4, 128)
    assert eng.output_tokens(80) == ref


def test_failed_run_releases_slots():
    """Legacy fault path (``isolate_flow_faults=False``): a user hook
    raising out of the live event loop tears the run down, but must not
    leak bound pool slots — the failed run releases its requests and the
    engine stays serviceable.  (With the default per-flow isolation the
    same hook exception quarantines only its own flow: tests/
    test_faults.py.)"""
    cfg, params, eng = _tiny_real_engine(pool_slots=2,
                                         isolate_flow_faults=False)
    rng = np.random.default_rng(67)
    reqs = _mk_requests(cfg, rng, [0.0, 0.0], [12, 14], 8)
    state = {"n": 0}

    def boom(req, tok):
        state["n"] += 1
        if state["n"] >= 3:
            raise RuntimeError("user callback exploded")

    for r in reqs:
        eng.submit(r, on_token=boom)
    with pytest.raises(RuntimeError, match="exploded"):
        eng.run()
    be = eng.backend
    assert not be._slot and len(be._free) == be.pool_slots
    # the same engine serves a fresh trace token-exactly afterwards
    reqs2 = _mk_requests(cfg, rng, [0.0, 0.0], [12, 14], 4)
    for i, r in enumerate(reqs2):
        r.id = 100 + i
    eng.serve(copy.deepcopy(reqs2))
    for r in reqs2:
        ref = _reference_tokens(cfg, params, r.tokens, 4, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"


# -- release/rebind safety (satellite bugfix check) --------------------------
def test_release_mid_prefill_no_double_free_and_clean_rebind():
    """A request released mid-prefill (slot returned at PR 3's
    slot-at-prefill-start lifetime) cannot double-release its slot, and the
    row rebinds cleanly even when the pool grows before the rebind.  The
    slot lifetime under test is the in-pool one, so the single-device
    backend is pinned (a staged prefill binds no slot before handoff)."""
    cfg, params, eng = _tiny_real_engine(pool_slots=1, dual_device=False)
    be = eng.backend
    rng = np.random.default_rng(61)
    a, b, c = _mk_requests(cfg, rng, [0.0] * 3, [24, 20, 16], 3)
    be.register(a)
    be.prefill_chunk(a, 0, 16, 0.0)  # slot 0 bound mid-prefill
    assert a.id in be._slot
    be.release([a], 0.0)
    assert a.id not in be._slot and sorted(be._free) == [0]
    be.release([a], 0.0)  # double release must be a no-op
    be.finish(a, 0.0)  # ...and so must a stray finish
    assert sorted(be._free) == [0], "slot double-freed"
    # rebind the freed slot, then grow the pool mid-prefill
    be.register(b)
    be.prefill_chunk(b, 0, 20, 0.0)  # takes slot 0
    be.register(c)
    be.prefill_chunk(c, 0, 16, 0.0)  # no free slot -> growth to 2
    assert be.pool_slots == 2
    be.prefill_done(b, 0.0)
    be.prefill_done(c, 0.0)
    for _ in range(2):
        be.decode_iteration([b, c], 0.0)
    for r in (b, c):
        ref = _reference_tokens(cfg, params, r.tokens, 3, 128)
        assert be.output_tokens(r.id) == ref, f"req {r.id}"
    # slot accounting stays exact: every slot is either free or bound
    assert len(be._free) + len(be._slot) == be.pool_slots
    # the released request itself re-serves cleanly end to end
    eng.serve([copy.deepcopy(a)])
    ref = _reference_tokens(cfg, params, a.tokens, 3, 128)
    assert eng.output_tokens(a.id) == ref
