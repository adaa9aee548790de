"""Stage-decoupled dual-device execution (DESIGN.md §14): staged prefill
on a second JAX device hands KV rows into the decode pool token-exactly
(mixed preemption/prefix-hit traces, mid-prefill release, cancel right
after handoff), elastic binding falls back to co-located execution under
backpressure, mesh construction fails typed on short device lists, and
the contention calibration threads through the scheduler without
perturbing the sim==real trace invariant.

Runs on one device (the engine tests then ask for co-location
explicitly — ``prefill_device`` = the decode device — and the inherited
path must stay byte-identical; asking for the dual backend without a
second device raises) and on the pinned two-device CI leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``
+ ``REPRO_EXPECT_TWO_DEVICES=1``, where a silently single-device jax
must FAIL, not skip)."""
import copy
import os

import numpy as np
import pytest

from repro.core import AgentXPUEngine, Priority, Request
from repro.core.contention import (CoExecutionCalibration,
                                   MemoryPressureEstimator,
                                   co_execution_rates)

EXPECT_TWO = os.environ.get("REPRO_EXPECT_TWO_DEVICES", "") not in ("", "0")

_STATE = {}


def _n_devices():
    import jax
    return len(jax.devices())


def _require_two():
    n = _n_devices()
    if n >= 2:
        return
    if EXPECT_TWO:
        pytest.fail(f"REPRO_EXPECT_TWO_DEVICES=1 but jax sees {n} device(s)"
                    f" — the CI leg's XLA_FLAGS did not take effect")
    pytest.skip("needs 2 JAX devices "
                "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")


def _cfg_params():
    if "cfg" not in _STATE:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_tiny_config
        from repro.models import init_params
        cfg = get_tiny_config("llama3-405b")
        _STATE["cfg"] = cfg
        _STATE["params"] = init_params(cfg, jax.random.PRNGKey(0),
                                       jnp.float32)
    return _STATE["cfg"], _STATE["params"]


def _colocate_unless_two():
    """The dual backend needs a second device; with one, co-location is
    requested explicitly (prefill on the decode device)."""
    import jax
    return {} if _n_devices() >= 2 else {"prefill_device": jax.devices()[0]}


def _real_engine(dual, **kw):
    from repro.core.engine import RealAgentXPUEngine
    cfg, params = _cfg_params()
    if dual:
        kw = {**_colocate_unless_two(), **kw}
    return cfg, params, RealAgentXPUEngine(cfg, params, dual_device=dual,
                                           **kw)


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


def _mixed_trace(cfg, plen=160, out=6):
    """Bench-shaped exactness trace: multi-chunk proactive prefills (plen >
    the HEG's 128-token chunk), a flow repeating flow 0's prompt so its
    prefix hit must come off the decode pool, and reactives arriving
    mid-prefill / mid-decode."""
    def pro(i, arrival=0.0, seed=None):
        return Request(
            id=i, priority=Priority.PROACTIVE, prompt_len=plen,
            max_new_tokens=out, arrival_time=arrival,
            tokens=np.random.default_rng(seed if seed is not None
                                         else i).integers(
                0, cfg.vocab_size, (1, plen)))

    reqs = [pro(0), pro(1)]
    reqs.append(pro(8, arrival=0.003, seed=0))  # duplicate of flow 0
    for k, t in ((0, 0.0008), (1, 0.004)):
        reqs.append(Request(
            id=20 + k, priority=Priority.REACTIVE, prompt_len=16,
            max_new_tokens=4, arrival_time=t,
            tokens=np.random.default_rng(100 + k).integers(
                0, cfg.vocab_size, (1, 16))))
    return reqs


# -- CI-leg wiring ------------------------------------------------------------
def test_ci_leg_sees_two_devices():
    """On the dedicated dual-device CI leg the forced host-platform device
    count must actually be visible — a mis-ordered jax import would
    otherwise quietly turn every staged-path test into a skip."""
    if not EXPECT_TWO:
        pytest.skip("only meaningful with REPRO_EXPECT_TWO_DEVICES=1")
    assert _n_devices() >= 2


# -- mesh construction (typed device-count failures) --------------------------
def test_production_mesh_raises_typed_on_short_device_list():
    from repro.launch.mesh import MeshDeviceError, make_production_mesh
    with pytest.raises(MeshDeviceError) as ei:
        make_production_mesh()
    assert ei.value.requested == 256
    assert ei.value.available == _n_devices()
    assert "XLA_FLAGS" in str(ei.value)  # actionable, not a numpy reshape
    assert isinstance(ei.value, RuntimeError)  # old callers still catch


def test_dual_device_mesh_and_stage_order():
    import jax
    from repro.launch.mesh import (MeshDeviceError, dual_stage_devices,
                                   make_dual_device_mesh)
    if _n_devices() < 2:
        with pytest.raises(MeshDeviceError) as ei:
            make_dual_device_mesh()
        assert (ei.value.requested, ei.value.available) == (2, 1)
        return
    mesh = make_dual_device_mesh()
    assert mesh.axis_names == ("stage",)
    assert mesh.devices.size == 2
    dec, pf = dual_stage_devices()
    # decode keeps device 0: enabling dual mode never migrates the pool
    assert dec == jax.devices()[0]
    assert pf == jax.devices()[1]
    assert dec != pf


def test_forced_dual_device_raises_without_second_device():
    """``dual_device=True`` with one device and no ``prefill_device`` must
    fail typed instead of quietly co-locating."""
    from repro.core.engine import RealAgentXPUEngine
    from repro.launch.mesh import MeshDeviceError
    if _n_devices() >= 2:
        pytest.skip("only meaningful with a single device")
    cfg, params = _cfg_params()
    with pytest.raises(MeshDeviceError):
        RealAgentXPUEngine(cfg, params, max_len=64, pool_slots=2,
                           dual_device=True)


# -- token exactness: dual vs single on the mixed trace -----------------------
def test_dual_engine_token_exact_mixed_trace():
    """Every flow of the mixed preemption/prefix-hit trace streams
    byte-identical tokens from the dual-device engine and the
    single-device engine, and matches the unscheduled reference."""
    kw = dict(max_len=256, pool_slots=6, decode_segment_steps=4)
    cfg, params, eng_dual = _real_engine(True, **kw)
    _, _, eng_single = _real_engine(False, **kw)
    reqs = _mixed_trace(cfg)
    eng_dual.serve(copy.deepcopy(reqs))
    eng_single.serve(copy.deepcopy(reqs))
    for r in reqs:
        assert eng_dual.output_tokens(r.id) == \
            eng_single.output_tokens(r.id), f"req {r.id}"
    ref = _reference_tokens(cfg, params, reqs[0].tokens, 6, 256)
    assert eng_dual.output_tokens(0) == ref
    ref = _reference_tokens(cfg, params, reqs[3].tokens, 4, 256)
    assert eng_dual.output_tokens(20) == ref
    assert eng_dual.backend.validate() == []
    st = eng_dual.stats()
    # contention observability rides the same stats dict (satellite of §14)
    assert "contention_pressure_peak" in st
    assert st["co_execution_decode_slowdown_model"] >= 1.0
    if _n_devices() >= 2:
        assert st["dual_device"]
        assert st["staged_prefills"] > 0  # cold prompts really staged
        assert st["handoff_device_calls"] > 0
        assert st["kv_bytes_handoff"] > 0
        assert st["colocated_hits"] >= 1  # the duplicate-prompt flow
        assert st["prefill_device"] != st["decode_device"]
    else:
        assert not st["dual_device"]  # explicitly co-located


def test_sim_and_real_dual_traces_identical_with_aborts():
    """Stage decoupling is backend-local: the kernel-completion trace of a
    sim run and a dual-device real run stays identical when a reactive
    abort fires mid-plan (the §14 sim==real invariant)."""
    cfg, params, eng_real = _real_engine(True, max_len=128, pool_slots=8,
                                         decode_segment_steps=2)
    rng = np.random.default_rng(43)
    pro = [Request(id=i, priority=Priority.PROACTIVE, prompt_len=plen,
                   max_new_tokens=16, arrival_time=0.0,
                   tokens=rng.integers(0, cfg.vocab_size, (1, plen)))
           for i, plen in enumerate([14, 12])]
    eng_probe = AgentXPUEngine(cfg, decode_segment_steps=2)
    eng_probe.run_trace(copy.deepcopy(pro))
    steps = [t for kind, _, t in eng_probe.last_trace
             if kind == "decode_step"]
    reqs = pro + [Request(
        id=9, priority=Priority.REACTIVE, prompt_len=10, max_new_tokens=4,
        arrival_time=steps[int(len(steps) * 0.4)],
        tokens=rng.integers(0, cfg.vocab_size, (1, 10)))]
    eng_sim = AgentXPUEngine(cfg, decode_segment_steps=2)
    m_sim = eng_sim.run_trace(copy.deepcopy(reqs))
    m_real = eng_real.serve(copy.deepcopy(reqs))
    assert eng_real.stats()["aborted_runs"] > 0
    assert eng_sim.last_trace == eng_real.last_trace
    assert m_sim.sim_time == m_real.sim_time


def test_same_kind_devices_stage_despite_heg_affinity():
    """The HEG's lane tables price an NPU against an iGPU.  Two devices of
    one kind must still stage a cold prompt when those tables price the
    prefill lane out, as they do at full Llama-3.2-3B widths."""
    _require_two()
    from repro.core.backend import DualDeviceBackend

    class SlowPrefillLane:
        def prefill_time_estimate(self, prompt_len, lane="npu"):
            return 2.0 if lane == "npu" else 1.0

    cfg, params = _cfg_params()
    be = DualDeviceBackend(cfg, params, pool_slots=2, max_len=256,
                           heg=SlowPrefillLane())
    r = Request(id=1, priority=Priority.PROACTIVE, prompt_len=160,
                max_new_tokens=4, arrival_time=0.0,
                tokens=np.random.default_rng(5).integers(
                    0, cfg.vocab_size, (1, 160)))
    be.register(r)
    be.prefill_chunk(r, 0, 128, 0.0)
    assert 1 in be._staged
    assert be.colocated_affinity == 0
    be.release([r], 0.0)
    assert be.validate() == []


# -- KV handoff lifecycle (direct backend drive, 2 devices) -------------------
def test_staged_release_mid_prefill_and_handoff_cancel():
    """A staged flow released mid-prefill leaves no slot, scratch, or
    staging residue; a flow cancelled immediately after its handoff frees
    its pool row; and the next flow binding that row prefills to the
    correct first token (no stale KV)."""
    _require_two()
    from repro.core.backend import DualDeviceBackend
    cfg, params = _cfg_params()
    be = DualDeviceBackend(cfg, params, pool_slots=2, max_len=256)
    assert be.dual_device
    rng = np.random.default_rng(7)

    def mk(rid):
        return Request(id=rid, priority=Priority.PROACTIVE, prompt_len=160,
                       max_new_tokens=4, arrival_time=0.0,
                       tokens=rng.integers(0, cfg.vocab_size, (1, 160)))

    # mid-prefill release: first chunk ran on the prefill device
    r1 = mk(1)
    be.register(r1)
    be.prefill_chunk(r1, 0, 128, 0.0)
    assert 1 in be._staged and 1 in be._scratch
    be.release([r1], 0.0)
    assert not be._staged and 1 not in be._scratch
    assert not be._stage_decision and 1 not in be._tok_dev_pf
    assert len(be._free) == 2  # staged prefill binds no slot before handoff
    assert be.validate() == []

    # cancel right after the handoff committed the row
    r2 = mk(2)
    be.register(r2)
    be.prefill_chunk(r2, 0, 128, 0.0)
    be.prefill_chunk(r2, 128, 32, 0.0)
    be.prefill_done(r2, 0.0)
    assert be.handoff_device_calls == 1
    ref2 = _reference_tokens(cfg, params, r2.tokens, 1, 256)
    assert be.output_tokens(2) == ref2  # handed-off first token is exact
    be.finish(r2, 0.0)
    assert len(be._free) == 2
    assert be.validate() == []

    # the freed row rebinds with no stale KV: a different prompt through
    # the same staging path lands its own exact first token
    r3 = mk(3)
    be.register(r3)
    be.prefill_chunk(r3, 0, 128, 0.0)
    be.prefill_chunk(r3, 128, 32, 0.0)
    be.prefill_done(r3, 0.0)
    assert be.output_tokens(3) == _reference_tokens(cfg, params, r3.tokens,
                                                    1, 256)
    be.release([r3], 0.0)
    assert be.validate() == []


def test_backpressure_colocates_second_prefill():
    """With the staging queue capped at one in-flight prefill, a second
    concurrent prefill elastically binds to the decode device (the
    inherited in-pool path) instead of queuing behind the first."""
    _require_two()
    from repro.core.backend import DualDeviceBackend
    cfg, params = _cfg_params()
    be = DualDeviceBackend(cfg, params, pool_slots=3, max_len=256,
                           prefill_inflight_max=1)
    rng = np.random.default_rng(11)
    reqs = [Request(id=i, priority=Priority.PROACTIVE, prompt_len=160,
                    max_new_tokens=4, arrival_time=0.0,
                    tokens=rng.integers(0, cfg.vocab_size, (1, 160)))
            for i in (1, 2)]
    for r in reqs:
        be.register(r)
    be.prefill_chunk(reqs[0], 0, 128, 0.0)
    be.prefill_chunk(reqs[1], 0, 128, 0.0)
    assert be._stage_decision == {1: True, 2: False}
    assert be.colocated_backpressure == 1
    assert len(be._free) == 2  # the co-located flow bound its slot already
    # the decision is sticky: finishing flow 1 does not migrate flow 2
    be.prefill_chunk(reqs[0], 128, 32, 0.0)
    be.prefill_done(reqs[0], 0.0)
    be.prefill_chunk(reqs[1], 128, 32, 0.0)
    assert be._stage_decision[2] is False
    be.prefill_done(reqs[1], 0.0)
    for r in reqs:
        assert be.output_tokens(r.id) == _reference_tokens(
            cfg, params, r.tokens, 1, 256), f"req {r.id}"
    be.release(reqs, 0.0)
    assert be.validate() == []


# -- contention model / calibration (no JAX) ----------------------------------
def test_co_execution_rates_and_estimator():
    assert co_execution_rates([0.3, 0.4]) == [1.0, 1.0]  # uncontended
    rp, rd = co_execution_rates([0.35, 0.85])
    assert rp < 1.0 and rd < 1.0
    assert rd < rp  # the memory-bound decode kernel suffers more
    est = MemoryPressureEstimator()
    est.add("prefill", 0.35)
    est.add("decode", 0.85)
    assert est.pressure == pytest.approx(1.20)
    assert est.active == {"prefill": 0.35, "decode": 0.85}
    assert est.rates() == co_execution_rates([0.35, 0.85])
    est.remove("prefill")
    assert est.pressure == pytest.approx(0.85)
    assert est.rates() == [1.0]


def test_calibration_sources():
    neutral = CoExecutionCalibration.neutral()
    assert (neutral.prefill_slowdown, neutral.decode_slowdown) == (1.0, 1.0)
    model = CoExecutionCalibration.from_rates(0.35, 0.85)
    assert model.prefill_slowdown > 1.0 and model.decode_slowdown > 1.0
    # measured slowdown wins over the bandwidth model when present
    cal = CoExecutionCalibration.from_backend_stats(
        {"co_execution_decode_slowdown_measured": 1.3,
         "prefill_bw_util": 0.35, "decode_bw_util": 0.85})
    assert cal.decode_slowdown == pytest.approx(1.3)
    assert cal.prefill_slowdown == pytest.approx(model.prefill_slowdown)
    # no measurement yet -> the model (or an explicit default) stands in
    cal = CoExecutionCalibration.from_backend_stats(
        {"co_execution_decode_slowdown_measured": None,
         "prefill_bw_util": 0.35, "decode_bw_util": 0.85})
    assert cal == model
    assert CoExecutionCalibration.from_backend_stats(
        {}, default=neutral) == neutral


def test_calibration_threads_into_scheduler_neutrally():
    """The scheduler consumes the calibration in its piggyback-horizon
    arithmetic; the neutral default keeps every sim trace bit-identical
    (the invariant the real engine's trace equality rests on), while a
    pessimistic decode slowdown can only shrink fused plans."""
    cfg, _ = _cfg_params()
    rng = np.random.default_rng(47)
    reqs = [Request(id=i, priority=Priority.PROACTIVE, prompt_len=plen,
                    max_new_tokens=24, arrival_time=0.0,
                    tokens=rng.integers(0, cfg.vocab_size, (1, plen)))
            for i, plen in enumerate([12, 14, 16])]
    reqs.append(Request(
        id=9, priority=Priority.REACTIVE, prompt_len=96, max_new_tokens=4,
        arrival_time=0.004, tokens=rng.integers(0, cfg.vocab_size, (1, 96))))

    def run(**kw):
        eng = AgentXPUEngine(cfg, decode_segment_steps=2, **kw)
        eng.run_trace(copy.deepcopy(reqs))
        return eng

    base = run()
    assert base.last_sched.contention_cal == CoExecutionCalibration.neutral()
    explicit = run(contention_calibration=CoExecutionCalibration.neutral())
    assert base.last_trace == explicit.last_trace
    slow = run(contention_calibration=CoExecutionCalibration(
        prefill_slowdown=1.0, decode_slowdown=2.0))
    assert slow.last_sched.piggyback_steps <= base.last_sched.piggyback_steps
