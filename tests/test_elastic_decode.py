"""Elastic decode dispatch (DESIGN.md §9): live-prefix-bounded attention +
pow-2 live-row sub-pool decode must be TOKEN-EXACT against the full-pool
path in every regime — partial truncation, ring-wrap fallback, sliding
windows, pool growth with low-slot compaction, and PR 4 mid-run aborts —
while leaving the kernel-completion trace untouched (the backend changes
*what* runs, never *when*)."""
import copy

import numpy as np
import pytest

from repro.core import AgentXPUEngine, Priority, Request


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


def _tiny_real_engine(arch="llama3-405b", max_len=128, **kw):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_tiny_config
    from repro.core.engine import RealAgentXPUEngine
    from repro.models import init_params
    cfg = get_tiny_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params, RealAgentXPUEngine(cfg, params, max_len=max_len, **kw)


def test_elastic_bounds_engage_and_stay_exact():
    """Low occupancy on a large pool: the elastic dispatch really ran with
    rows < pool and kv_limit < max_len, streamed fewer KV bytes than the
    full-pool baseline, and produced identical tokens."""
    cfg, params, eng = _tiny_real_engine(pool_slots=16, b_max=16)
    _, _, eng_full = _tiny_real_engine(pool_slots=16, b_max=16,
                                       elastic_decode=False)
    rng = np.random.default_rng(71)
    reqs = _mk_requests(cfg, rng, [0.0] * 3, [12, 14, 16], 8)
    eng.serve(copy.deepcopy(reqs))
    eng_full.serve(copy.deepcopy(reqs))
    st, stf = eng.stats(), eng_full.stats()
    assert 0 < st["decode_rows"] <= 4  # next_pow2(high slot 2 + 1), not 16
    assert 0 < st["decode_kv_limit"] <= 32  # pow-2 live prefix, not 128
    assert stf["decode_rows"] == 16 and stf["decode_kv_limit"] == 128
    assert 0 < st["kv_bytes_decode"] < stf["kv_bytes_decode"]
    for r in reqs:
        assert eng.output_tokens(r.id) == eng_full.output_tokens(r.id), \
            f"req {r.id}"
        ref = _reference_tokens(cfg, params, r.tokens, 8, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"


def test_ring_wrap_fallback_token_exact():
    """Decode past ``alloc``: positions wrap the ring mid-run, pushing the
    kv bound to max_len (truncation becomes the identity) while the early
    iterations still ran truncated — tokens stay exact throughout."""
    cfg, params, eng = _tiny_real_engine(max_len=32, pool_slots=4, b_max=4)
    _, _, eng_full = _tiny_real_engine(max_len=32, pool_slots=4, b_max=4,
                                       elastic_decode=False)
    rng = np.random.default_rng(73)
    # pos runs 8 -> 38 > alloc 32: early decode fits under kv_limit 16/32,
    # the tail wraps the ring and must fall back to the full view
    reqs = _mk_requests(cfg, rng, [0.0, 0.0], [8, 6], 30)
    eng.serve(copy.deepcopy(reqs))
    eng_full.serve(copy.deepcopy(reqs))
    st = eng.stats()
    assert st["decode_kv_limit"] == 32  # the final dispatches fell back
    for r in reqs:
        assert eng.output_tokens(r.id) == eng_full.output_tokens(r.id), \
            f"req {r.id}"
        ref = _reference_tokens(cfg, params, r.tokens, 30, 32)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"


def test_sliding_window_config_elastic_exact():
    """A windowed hybrid arch (recurrentgemma-9b tiny: RG-LRU + local
    attention, window 32 < max_len): window-shrunk ring leaves are never
    truncated, recurrent states ride the row bound only — elastic output
    matches the full-pool path and the unscheduled reference."""
    cfg, params, eng = _tiny_real_engine(arch="recurrentgemma-9b",
                                         pool_slots=8, b_max=8)
    _, _, eng_full = _tiny_real_engine(arch="recurrentgemma-9b",
                                       pool_slots=8, b_max=8,
                                       elastic_decode=False)
    assert cfg.sliding_window == 32
    rng = np.random.default_rng(79)
    # prompts long enough that the 32-token window actually slides
    reqs = _mk_requests(cfg, rng, [0.0, 0.0], [40, 36], 10)
    eng.serve(copy.deepcopy(reqs))
    eng_full.serve(copy.deepcopy(reqs))
    assert 0 < eng.stats()["decode_rows"] <= 2  # row bound engaged
    for r in reqs:
        assert eng.output_tokens(r.id) == eng_full.output_tokens(r.id), \
            f"req {r.id}"
        ref = _reference_tokens(cfg, params, r.tokens, 10, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"


def test_growth_and_low_slot_compaction_elastic():
    """Pool growth mid-run on the donated pool, then a second wave that
    rebinds the LOWEST freed slots: the elastic row bound tracks occupancy
    back down after the pool doubled, tokens exact in both waves."""
    cfg, params, eng = _tiny_real_engine(pool_slots=2)
    rng = np.random.default_rng(83)
    wave1 = _mk_requests(cfg, rng, [0.0] * 3, [12, 14, 16], 6)
    eng.serve(copy.deepcopy(wave1))
    assert eng.stats()["pool_slots"] == 4  # grew past the initial 2
    for r in wave1:
        ref = _reference_tokens(cfg, params, r.tokens, 6, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"
    # wave 2: two requests on the grown-but-now-empty pool take slots 0/1
    # (min-heap), so decode dispatches over 2 rows, not 4
    wave2 = _mk_requests(cfg, rng, [0.0, 0.0], [15, 13], 6)
    for i, r in enumerate(wave2):
        r.id = 100 + i
    eng.serve(copy.deepcopy(wave2))
    st = eng.stats()
    assert st["pool_slots"] == 4
    assert 0 < st["decode_rows"] <= 2  # compacted: half the pool is dead
    for r in wave2:
        ref = _reference_tokens(cfg, params, r.tokens, 6, 128)
        assert eng.output_tokens(r.id) == ref, f"req {r.id}"


def _mid_decode_time(cfg, reqs, frac=0.4, **sched_kw):
    eng = AgentXPUEngine(cfg, **sched_kw)
    eng.run_trace(copy.deepcopy(reqs))
    steps = [t for kind, _, t in eng.last_trace if kind == "decode_step"]
    assert steps, "trace has no decode phase"
    return steps[int(len(steps) * frac)]


def test_elastic_exact_through_mid_run_abort():
    """A reactive arrival truncates a committed fused plan at a segment
    boundary (PR 4): the elastic and full-pool backends replay the same
    buffered rows, keep identical kernel traces, and stay token-exact."""
    cfg, params, eng = _tiny_real_engine(decode_segment_steps=2)
    _, _, eng_full = _tiny_real_engine(decode_segment_steps=2,
                                       elastic_decode=False)
    rng = np.random.default_rng(89)
    pro = _mk_requests(cfg, rng, [0.0] * 3, [12, 14, 16], 24)
    t_mid = _mid_decode_time(cfg, pro, frac=0.3, decode_segment_steps=2)
    reactive = Request(
        id=50, priority=Priority.REACTIVE, prompt_len=12, max_new_tokens=6,
        arrival_time=t_mid, tokens=rng.integers(0, cfg.vocab_size, (1, 12)))
    reqs = pro + [reactive]
    eng.serve(copy.deepcopy(reqs))
    eng_full.serve(copy.deepcopy(reqs))
    assert eng.stats()["aborted_runs"] > 0  # the plan really was cut
    assert eng_full.stats()["aborted_runs"] > 0
    assert eng.last_trace == eng_full.last_trace  # scheduling is invariant
    for r in reqs:
        assert eng.output_tokens(r.id) == eng_full.output_tokens(r.id), \
            f"req {r.id}"


def test_sim_trace_invariant_to_elasticity():
    """Elasticity changes what the backend executes, never when: the sim
    trace, the elastic real trace and the full-pool real trace are one."""
    cfg, params, eng = _tiny_real_engine()
    _, _, eng_full = _tiny_real_engine(elastic_decode=False)
    rng = np.random.default_rng(97)
    reqs = _mk_requests(cfg, rng, [0.0, 0.02, 0.04], [20, 14, 17], 4,
                        reactive=(1,))
    eng_sim = AgentXPUEngine(cfg)
    m_sim = eng_sim.run_trace(copy.deepcopy(reqs))
    m_el = eng.serve(copy.deepcopy(reqs))
    m_full = eng_full.serve(copy.deepcopy(reqs))
    assert len(m_sim.completed) == len(m_el.completed) == 3
    assert eng_sim.last_trace == eng.last_trace == eng_full.last_trace
    assert m_sim.sim_time == m_el.sim_time == m_full.sim_time


@pytest.mark.parametrize("n_steps", [1, 8], ids=["decode", "decode_run"])
@pytest.mark.parametrize("kv", ["bf16", "int8", "window_wrapped"])
def test_masked_rows_state_bit_identical(kv, n_steps):
    """Bound-but-inactive slots below the row bound are computed but leave
    no trace: the pooled decode program's in-place scatter drops their
    writes, so K, V, ``slot_pos`` and the int8 scales, their position and
    recurrent state stay bit-identical, as do the rows past the bound —
    while the active rows do advance."""
    import jax
    import jax.numpy as jnp
    from jax.tree_util import DictKey, tree_flatten_with_path
    from repro.configs import get_tiny_config
    from repro.core.backend import decode_program
    from repro.models import init_cache, init_params
    arch = "recurrentgemma-9b" if kv == "window_wrapped" else "llama3-405b"
    cfg = get_tiny_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pool_size, max_len, rows = 8, 64, 4
    rng = np.random.default_rng(101)
    if kv == "window_wrapped":
        # window 32 < max_len: rings of 32 slots, every row past a wrap
        pos = rng.integers(33, max_len - n_steps, pool_size)
        kv_limit = max_len
    else:
        pos = rng.integers(0, 32 - n_steps, pool_size)
        kv_limit = 32
    active = np.array([True, False, True, False] + [False] * 4)
    pool = init_cache(cfg, params, pool_size, max_len, jnp.float32,
                      kv_dtype="int8" if kv == "int8" else None)

    def fill(path, x):
        name = path[-1].key if isinstance(path[-1], DictKey) else ""
        if name == "pos":
            return jnp.asarray(pos, jnp.int32)
        if name == "slot_pos":  # the latest position held by each slot
            s = np.arange(x.shape[-1])
            held = s + (pos[:, None] - 1 - s) // x.shape[-1] * x.shape[-1]
            return jnp.asarray(np.broadcast_to(np.where(held >= 0, held, -1),
                                               x.shape), jnp.int32)
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, x.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(x.shape), x.dtype)
    pool = jax.tree_util.tree_map_with_path(fill, pool)
    before, _ = tree_flatten_with_path(
        jax.tree_util.tree_map(np.asarray, pool))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, pool_size), jnp.int32)
    fn = decode_program(cfg, "xla", max_len, pool_size, rows, kv_limit,
                        None if n_steps == 1 else n_steps)
    _, _, new = jax.jit(fn)(params, pool, toks, jnp.asarray(active))
    after, _ = tree_flatten_with_path(new)
    still = np.flatnonzero(~active)  # rows 1 and 3, and 4.. past the bound
    names = set()
    for (path, old), (_, cur) in zip(before, after):
        axis = 1 if path[0].key == "blocks" else 0
        names.add(path[-1].key)
        np.testing.assert_array_equal(np.take(np.asarray(cur), still, axis),
                                      np.take(old, still, axis),
                                      err_msg=jax.tree_util.keystr(path))
    assert {"k", "v", "slot_pos"} <= names
    assert kv != "int8" or {"k_scale", "v_scale"} <= names
    assert kv != "window_wrapped" or {"h", "conv"} <= names
    np.testing.assert_array_equal(np.asarray(new["pos"]),
                                  pos + n_steps * active)
