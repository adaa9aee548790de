"""Quantized KV hot path (DESIGN.md §11): symmetric int8 round-trip bounds,
scale write/read exactness through the slot-pool ring, quantized-vs-bf16
logit error on a tiny config, prefix-cache hits on a quantized pool, and
serving-level token parity across the (kv_dtype, kernel_backend) matrix.

Everything runs on the plain f32 exactness baseline unless a test opts a
cache or engine into ``kv_dtype="int8"`` / ``kernel_backend="pallas"`` —
the defaults stay byte-identical to the pre-quantization code paths."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.core import Priority, Request
from repro.models import (dequantize_kv, extend, init_cache, init_params,
                          kv_supports_int8, quantize_kv)


# -- pure quantizer ----------------------------------------------------------
def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 4, 32), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]  # per-(slot, kv head), not per-tensor
    err = jnp.abs(dequantize_kv(q, s) - x)
    # symmetric round-to-nearest: every element within half a step
    assert bool(jnp.all(err <= s[..., None] / 2 + 1e-7))
    # the max-magnitude element per (…, head) group maps to ±127 exactly
    amax = jnp.max(jnp.abs(x), axis=-1)
    assert bool(jnp.all(jnp.max(jnp.abs(q), axis=-1) == 127))
    assert np.allclose(np.asarray(s), np.asarray(amax) / 127.0)


def test_quantize_exact_on_grid_values():
    # values already on the int8 grid quantize back to exactly their grid
    # codes; the derived scale (127*s0)/127 may differ from s0 in the last
    # ulp, so the round trip is exact against q0 * s, not against x
    q0 = jax.random.randint(jax.random.PRNGKey(1), (2, 5, 2, 16), -127, 128,
                            jnp.int32)
    # force a ±127 in every head group so the derived scale matches s0
    q0 = q0.at[..., 0].set(127)
    s0 = jax.random.uniform(jax.random.PRNGKey(2), (2, 5, 2), jnp.float32,
                            0.01, 1.0)
    x = q0.astype(jnp.float32) * s0[..., None]
    q, s = quantize_kv(x)
    assert bool(jnp.all(q == q0.astype(jnp.int8)))
    assert np.allclose(np.asarray(s), np.asarray(s0), rtol=1e-6)
    assert bool(jnp.all(dequantize_kv(q, s)
                        == q0.astype(jnp.float32) * s[..., None]))


# -- scale round trip through the pool write path ----------------------------
def _attn_states(cache):
    for st in (*cache["head"], *cache["blocks"].values(), *cache["tail"]):
        if "k" in st:
            yield st


def _fill_ring(cache, seed, alloc, pos_start=0):
    """Hand-fill every attention ring with random quantized content — the
    pool helpers must move these bytes verbatim, so bit-exact equality is
    the assertion, not a tolerance."""
    key = jax.random.PRNGKey(seed)
    for st in _attn_states(cache):
        for name in ("k", "v"):
            key, a, b = jax.random.split(key, 3)
            st[name] = jax.random.randint(
                a, st[name].shape, -127, 128, jnp.int32).astype(jnp.int8)
            st[name + "_scale"] = jax.random.uniform(
                b, st[name + "_scale"].shape, jnp.float32, 0.01, 1.0)
        st["slot_pos"] = jnp.broadcast_to(
            pos_start + jnp.arange(alloc, dtype=jnp.int32),
            st["slot_pos"].shape)
    cache["pos"] = jnp.full_like(cache["pos"], pos_start + alloc)


def _ring_axis(st):
    return st["slot_pos"].ndim - 1


def _quant_pool_and_row(batch=3, max_len=32, seed=5):
    from repro.models import kvcache as KC
    cfg = get_tiny_config("llama3-405b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pool = init_cache(cfg, params, batch, max_len, jnp.float32,
                      kv_dtype="int8")
    one = init_cache(cfg, params, 1, max_len, jnp.float32, kv_dtype="int8")
    _fill_ring(one, seed, max_len)
    return KC, pool, one


_QLEAVES = ("k", "v", "k_scale", "v_scale", "slot_pos")


def test_write_slot_read_row_roundtrip_bit_exact():
    """``write_slot`` -> ``read_row`` round trip through an int8 pool is
    bit-exact for payload AND scales, and leaves other rows untouched."""
    KC, pool, one = _quant_pool_and_row()
    pool = KC.write_slot(pool, one, 1)
    back = KC.read_row(pool, 1)
    for st_o, st_b in zip(_attn_states(one), _attn_states(back)):
        for name in _QLEAVES:
            assert st_b[name].dtype == st_o[name].dtype
            assert bool(jnp.all(st_b[name] == st_o[name]))
    for st in _attn_states(KC.read_row(pool, 0)):  # neighbor rows untouched
        assert bool(jnp.all(st["k_scale"] == 0))
        assert bool(jnp.all(st["slot_pos"] == -1))


def test_write_row_slice_moves_scales_with_payload():
    """The chunked in-pool write path scatters exactly the chunk's ring
    positions — scales travel with their int8 payload, slot-for-slot."""
    KC, pool, one = _quant_pool_and_row()
    _, _, upd = _quant_pool_and_row(seed=9)
    pool = KC.write_slot(pool, one, 1)
    pool = KC.write_row_slice(pool, upd, 1, 4, 8)
    back = KC.read_row(pool, 1)
    idx = (4 + np.arange(8)) % 32
    keep = np.setdiff1d(np.arange(32), idx)
    for st_o, st_u, st_b in zip(_attn_states(one), _attn_states(upd),
                                _attn_states(back)):
        ax = _ring_axis(st_o)
        for name in _QLEAVES:
            got = np.asarray(st_b[name])
            assert (np.take(got, idx, ax) ==
                    np.take(np.asarray(st_u[name]), idx, ax)).all()
            assert (np.take(got, keep, ax) ==
                    np.take(np.asarray(st_o[name]), keep, ax)).all()


def test_prefix_copy_and_paste_carry_scales():
    """``copy_prefix_rows`` and the store path (``snapshot_prefix`` ->
    ``paste_prefix``) reproduce a quantized donor prefix bit-exactly: the
    first ``hit`` slots match payload+scales, the ``[hit, hit_cap)``
    overhang is masked to ``slot_pos == -1``."""
    KC, pool, one = _quant_pool_and_row()
    pool = KC.write_slot(pool, one, 0)
    hit, cap, full = 10, 16, 32

    def check(row_pool, dst):
        src, back = KC.read_row(row_pool, 0), KC.read_row(row_pool, dst)
        for st_s, st_b in zip(_attn_states(src), _attn_states(back)):
            ax = _ring_axis(st_s)
            lead = np.arange(hit)
            for name in ("k", "v", "k_scale", "v_scale"):
                assert (np.take(np.asarray(st_b[name]), lead, ax) ==
                        np.take(np.asarray(st_s[name]), lead, ax)).all()
            sp = np.asarray(st_b["slot_pos"])
            assert (np.take(sp, lead, ax) ==
                    np.take(np.asarray(st_s["slot_pos"]), lead, ax)).all()
            assert (np.take(sp, np.arange(hit, full), ax) == -1).all()

    check(KC.copy_prefix_rows(pool, 0, 2, hit, cap, full), 2)
    entry = KC.snapshot_prefix(pool, 0, cap, full)
    check(KC.paste_prefix(pool, entry, 1, hit, cap, cap, full), 1)


def test_int8_vs_plain_logit_error_small():
    """End-to-end logit drift from int8 KV stays tiny on the f32 baseline
    (per-head scales keep relative error ~2^-8) — and is nonzero, proving
    the quantized path actually engaged."""
    cfg = get_tiny_config("llama3-405b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 40), 0,
                              cfg.vocab_size, jnp.int32)
    plain = init_cache(cfg, params, 1, 64, jnp.float32)
    quant = init_cache(cfg, params, 1, 64, jnp.float32, kv_dtype="int8")
    lg_p, plain = extend(cfg, params, plain, toks)
    lg_q, quant = extend(cfg, params, quant, toks)
    diffs = [float(jnp.max(jnp.abs(lg_p - lg_q)))]
    for _ in range(4):  # decode steps read the whole mixed ring
        nxt = lg_p.argmax(-1)[:, None].astype(jnp.int32)
        lg_p, plain = extend(cfg, params, plain, nxt)
        lg_q, quant = extend(cfg, params, quant, nxt)
        diffs.append(float(jnp.max(jnp.abs(lg_p - lg_q))))
    assert 0.0 < max(diffs) < 0.05


def test_int8_unsupported_for_mla():
    cfg = get_tiny_config("deepseek-v2-lite-16b")
    assert not kv_supports_int8(cfg)
    assert kv_supports_int8(get_tiny_config("llama3-405b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(NotImplementedError):
        init_cache(cfg, params, 1, 64, jnp.float32, kv_dtype="int8")


# -- serving level: engines across the knob matrix ---------------------------
def _tiny_real_engine(**kw):
    from repro.core.engine import RealAgentXPUEngine
    cfg = get_tiny_config("llama3-405b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params, RealAgentXPUEngine(cfg, params, max_len=128, **kw)


def _mixed_reqs(cfg, n=4, out=4, shared=0):
    rng = np.random.default_rng(7)
    sys_toks = rng.integers(0, cfg.vocab_size, (1, shared)) if shared else \
        np.zeros((1, 0), np.int64)
    reqs = []
    for i in range(n):
        tail = 10 + 3 * i
        toks = np.concatenate(
            [sys_toks, rng.integers(0, cfg.vocab_size, (1, tail))], axis=1)
        reqs.append(Request(
            id=i, priority=Priority.REACTIVE if i % 2 else Priority.PROACTIVE,
            prompt_len=shared + tail, max_new_tokens=out,
            arrival_time=0.01 * i, tokens=toks))
    return reqs


def test_engine_validates_knobs():
    with pytest.raises(ValueError):
        _tiny_real_engine(kv_dtype="fp8")
    with pytest.raises(ValueError):
        _tiny_real_engine(kernel_backend="triton")


def test_stats_surface_quant_and_kernel_knobs():
    _, _, eng = _tiny_real_engine(kv_dtype="int8", kernel_backend="pallas")
    st = eng.stats()
    assert st["kv_dtype"] == "int8" and st["kernel_backend"] == "pallas"
    assert st["quant_scale_bytes"] > 0
    _, _, base = _tiny_real_engine()
    sb = base.stats()
    assert sb["kv_dtype"] == "bf16" and sb["kernel_backend"] == "xla"
    assert sb["quant_scale_bytes"] == 0


def test_serving_token_parity_across_knob_matrix():
    """xla/bf16 is the reference; pallas must match it token-exactly (same
    math, kernel-tiled), and int8 must be self-consistent across kernel
    backends (both dequantize the same stored (q, scale) pairs)."""
    outs = {}
    for kvd in ("bf16", "int8"):
        for kb in ("xla", "pallas"):
            cfg, _, eng = _tiny_real_engine(kv_dtype=kvd, kernel_backend=kb)
            eng.serve(copy.deepcopy(_mixed_reqs(cfg, n=4, out=4)))
            outs[(kvd, kb)] = [eng.output_tokens(i) for i in range(4)]
            assert all(len(t) == 4 for t in outs[(kvd, kb)])
    assert outs[("bf16", "pallas")] == outs[("bf16", "xla")]
    assert outs[("int8", "pallas")] == outs[("int8", "xla")]


def test_int8_fused_decode_matches_per_step():
    """Fusion invariance must survive quantization: a fused multi-step
    decode run over the int8 pool yields the same tokens as per-iteration
    dispatch (max_fused_steps=1)."""
    cfg, _, fused = _tiny_real_engine(kv_dtype="int8")
    _, _, step = _tiny_real_engine(kv_dtype="int8", max_fused_steps=1)
    reqs = _mixed_reqs(cfg, n=3, out=6)
    fused.serve(copy.deepcopy(reqs))
    step.serve(copy.deepcopy(reqs))
    for r in reqs:
        assert fused.output_tokens(r.id) == step.output_tokens(r.id)
    # fused dispatch really happened (fewer device calls than tokens)
    assert fused.stats()["decode_device_calls"] < \
        step.stats()["decode_device_calls"]


def test_prefix_cache_hits_on_quantized_pool():
    """Shared-prefix reuse (DESIGN.md §10) over an int8 pool: the COW row
    copy moves int8 payload + f32 scales verbatim, so hit-served flows are
    token-exact against a cold int8 engine and the hit accounting matches
    the bf16 pool's."""
    cfg, _, hot = _tiny_real_engine(kv_dtype="int8")
    _, _, cold = _tiny_real_engine(kv_dtype="int8", prefix_cache=False)
    reqs = _mixed_reqs(cfg, n=4, out=4, shared=40)
    hot.serve(copy.deepcopy(reqs))
    cold.serve(copy.deepcopy(reqs))
    for r in reqs:
        assert hot.output_tokens(r.id) == cold.output_tokens(r.id)
    h, c = hot.stats(), cold.stats()
    assert c["prefix_hits"] == 0
    assert h["prefix_hits"] == 3 and h["prefix_fallbacks"] == 0
    assert h["prefill_forward_tokens"] == \
        c["prefill_forward_tokens"] - h["prefix_hit_tokens"]
    # quantized rows shrink the copied-bytes accounting too
    assert 0 < h["kv_bytes_prefix_copied"]


def test_quantized_pool_shrinks_kv_bytes():
    """The headline byte win, measured at serving level: per-token decode
    KV traffic of the int8 pool is well under the 0.60x gate vs the plain
    pool (int8 payload + f32 per-head scales vs f32 payload here; the
    bf16-payload deployment ratio is checked in benchmarks/figures.py)."""
    cfg, _, plain = _tiny_real_engine()
    _, _, quant = _tiny_real_engine(kv_dtype="int8")
    reqs = _mixed_reqs(cfg, n=3, out=5)
    plain.serve(copy.deepcopy(reqs))
    quant.serve(copy.deepcopy(reqs))
    p, q = plain.stats(), quant.stats()
    # both engines decode the same token count, so the byte ratio IS the
    # per-token ratio
    assert 0 < q["kv_bytes_decode"] <= 0.60 * p["kv_bytes_decode"]
    # quantization must not cost extra dispatches on the decode hot path
    assert q["decode_device_calls"] == p["decode_device_calls"]
