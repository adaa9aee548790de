"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.blocks import clamp_block, kv_extent
from repro.models import attention as A
from repro.models import dequantize_kv, quantize_kv


@pytest.mark.parametrize("B,Hq,Hkv,S,hd,dtype", [
    (1, 4, 4, 128, 64, jnp.float32),   # MHA
    (2, 4, 2, 256, 64, jnp.float32),   # GQA 2:1
    (1, 8, 1, 128, 128, jnp.float32),  # MQA
    (1, 4, 2, 128, 64, jnp.bfloat16),  # bf16
])
def test_flash_attention_sweep(B, Hq, Hkv, S, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    o = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            block_q=64, block_k=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (2, 4, 2, 256, 64),
    (1, 8, 8, 128, 64),
    (3, 4, 1, 512, 128),
])
def test_decode_attention_sweep(B, Hq, Hkv, S, hd):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    valid = S * 3 // 4
    slot = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    slot = jnp.where(slot < valid, slot, -1)
    cur = jnp.full((B,), valid - 1, jnp.int32)
    o = ops.decode_attention(q, kc, vc, slot, cur, block_k=128)
    o_ref = ref.decode_attention_ref(q, kc, vc, slot, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_per_batch_positions():
    """Different cur_pos per batch row (ragged decode batch)."""
    B, Hq, Hkv, S, hd = 2, 4, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    slot = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cur = jnp.asarray([50, 100], jnp.int32)
    o = ops.decode_attention(q, kc, vc, slot, cur, block_k=64)
    o_ref = ref.decode_attention_ref(q, kc, vc, slot, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("BH,S,D,chunk", [
    (2, 64, 32, 16),
    (1, 128, 64, 32),
    (3, 96, 32, 32),  # padding path (96 % 32 == 0, uneven chunks count)
])
def test_rwkv6_scan_sweep(BH, S, D, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (BH, S, D)) * 0.5
    k = jax.random.normal(ks[1], (BH, S, D)) * 0.5
    v = jax.random.normal(ks[2], (BH, S, D)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (BH, S, D)) * 0.5)
    u = jax.random.normal(ks[4], (BH, 1, D)) * 0.3
    o, s = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    o_ref, s_ref = ref.rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (2, 128, 64, 64, 64),
    (1, 256, 128, 128, 64),
])
def test_rglru_scan_sweep(B, S, W, chunk, bw):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (B, S, W))
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, W))) * 0.5
    g = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, W)))
    h0 = jax.random.normal(ks[3], (B, W)) * 0.2
    hs, hf = ops.rglru_scan(x, a, g, h0, chunk=chunk, block_w=bw)
    hs_ref, hf_ref = ref.rglru_scan_ref(x, a, g, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("E,C,d,f", [(2, 64, 128, 64), (4, 128, 256, 128)])
def test_moe_gemm_sweep(E, C, d, f):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (E, C, d)) * 0.1
    w = jax.random.normal(ks[1], (E, d, f)) * 0.1
    y = ops.moe_gemm(x, w, block_c=64, block_f=64, block_d=64)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.moe_gemm_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


# ===== serving-path parity vs models.attention (the XLA reference) ==========
def _ring_cache(key, B, S, Hkv, hd, positions):
    """Random cache + slot_pos ring where slot i of row b holds absolute
    position positions[b][i] (-1 = empty)."""
    ks = jax.random.split(key, 2)
    kc = jax.random.normal(ks[0], (B, S, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    return kc, vc, jnp.asarray(positions, jnp.int32)


def test_decode_kernel_ring_wrap_vs_reference():
    """Wrapped ring: slot order is NOT position order (slot = pos % S)."""
    B, Hq, Hkv, S, hd = 2, 4, 2, 32, 64
    cur = jnp.asarray([40, 55], jnp.int32)  # both rows wrapped past S=32
    positions = [[(int(c) - S + 1 + i) % (2 ** 30) for i in range(S)]
                 for c in cur]
    # ring layout: position p lives in slot p % S
    positions = [[p for p in sorted(row, key=lambda p: p % S)]
                 for row in positions]
    kc, vc, slot = _ring_cache(jax.random.PRNGKey(7), B, S, Hkv, hd, positions)
    q = jax.random.normal(jax.random.PRNGKey(8), (B, Hq, hd), jnp.float32)
    o = ops.decode_attention(q, kc, vc, slot, cur, block_k=16)
    o_ref = A.decode_attention(q, kc, vc, slot, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [8, 24])
def test_decode_kernel_window_vs_reference(window):
    B, Hq, Hkv, S, hd = 1, 8, 2, 64, 32  # GQA 4:1
    slot = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cur = jnp.asarray([S - 1], jnp.int32)
    kc, vc, slot = _ring_cache(jax.random.PRNGKey(9), B, S, Hkv, hd,
                               np.asarray(slot))
    q = jax.random.normal(jax.random.PRNGKey(10), (B, Hq, hd), jnp.float32)
    o = ops.decode_attention(q, kc, vc, slot, cur, window=window, block_k=16)
    o_ref = A.decode_attention(q, kc, vc, slot, cur, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_kv_limit_vs_truncated_view():
    """Static kv_limit grid == the truncate_rings view the XLA path takes."""
    B, Hq, Hkv, S, hd, kvl = 2, 4, 2, 64, 32, 16
    live = 12  # every live position below kv_limit
    positions = [[i if i < live else -1 for i in range(S)] for _ in range(B)]
    kc, vc, slot = _ring_cache(jax.random.PRNGKey(11), B, S, Hkv, hd,
                               positions)
    q = jax.random.normal(jax.random.PRNGKey(12), (B, Hq, hd), jnp.float32)
    cur = jnp.full((B,), live - 1, jnp.int32)
    o = ops.decode_attention(q, kc, vc, slot, cur, kv_limit=kvl, block_k=8)
    o_view = A.decode_attention(q, kc[:, :kvl], vc[:, :kvl], slot[:, :kvl],
                                cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_view),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_int8_in_kernel_dequant():
    """int8 cache + scales through the kernel == dequantize-then-reference."""
    B, Hq, Hkv, S, hd = 2, 8, 2, 64, 32
    kc, vc, slot = _ring_cache(
        jax.random.PRNGKey(13), B, S, Hkv, hd,
        np.broadcast_to(np.arange(S)[None], (B, S)))
    qk, ks_ = quantize_kv(kc)
    qv, vs_ = quantize_kv(vc)
    q = jax.random.normal(jax.random.PRNGKey(14), (B, Hq, hd), jnp.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    o = ops.decode_attention(q, qk, qv, slot, cur, k_scale=ks_, v_scale=vs_,
                             block_k=16)
    o_ref = A.decode_attention(q, dequantize_kv(qk, ks_),
                               dequantize_kv(qv, vs_), slot, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def _pool_flash(q_bshd, k_bshd, v_bshd, pos_q, pos_kv, **kw):
    """ops.flash_attention_pool with model-layout tensors."""
    o = ops.flash_attention_pool(jnp.swapaxes(q_bshd, 1, 2),
                                 jnp.swapaxes(k_bshd, 1, 2),
                                 jnp.swapaxes(v_bshd, 1, 2),
                                 pos_q, pos_kv, **kw)
    return jnp.swapaxes(o, 1, 2)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_pool_gqa_vs_chunked_attention(Hq, Hkv):
    """Pool-row chunked prefill vs the serving XLA path, incl. GQA
    broadcasting and empty (-1) ring slots."""
    B, C, S, hd = 2, 16, 64, 32
    start = 20  # chunk positions [20, 36) against a ring holding [0, 36)
    ksr = jax.random.split(jax.random.PRNGKey(15), 3)
    q = jax.random.normal(ksr[0], (B, C, Hq, hd), jnp.float32)
    positions = [[i if i < start + C else -1 for i in range(S)]
                 for _ in range(B)]
    kc, vc, slot = _ring_cache(ksr[1], B, S, Hkv, hd, positions)
    pos_q = jnp.broadcast_to(start + jnp.arange(C)[None], (B, C))
    o = _pool_flash(q, kc, vc, pos_q, slot, block_q=8, block_k=16)
    o_ref = A.chunked_attention(q, kc, vc, causal=True, pos_q=pos_q,
                                pos_kv=slot, q_chunk=8, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_pool_ring_wrap_and_window():
    """Ring-wrapped positions + sliding window through the pool kernel."""
    B, C, Hq, Hkv, S, hd, window = 1, 8, 4, 2, 32, 32, 16
    cur0 = 48  # chunk [48, 56) on a ring of 32 -> slots hold [24, 56)
    ksr = jax.random.split(jax.random.PRNGKey(16), 2)
    q = jax.random.normal(ksr[0], (B, C, Hq, hd), jnp.float32)
    positions = [[(cur0 + C - S + i) for i in range(S)]]
    positions = [[p for p in sorted(row, key=lambda p: p % S)]
                 for row in positions]
    kc, vc, slot = _ring_cache(ksr[1], B, S, Hkv, hd, positions)
    pos_q = jnp.broadcast_to(cur0 + jnp.arange(C)[None], (B, C))
    o = _pool_flash(q, kc, vc, pos_q, slot, window=window,
                    block_q=8, block_k=8)
    o_ref = A.chunked_attention(q, kc, vc, causal=True, window=window,
                                pos_q=pos_q, pos_kv=slot,
                                q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_pool_int8_and_kv_limit():
    B, C, Hq, Hkv, S, hd, kvl = 1, 8, 4, 2, 64, 32, 32
    ksr = jax.random.split(jax.random.PRNGKey(17), 2)
    q = jax.random.normal(ksr[0], (B, C, Hq, hd), jnp.float32)
    live = 24
    positions = [[i if i < live else -1 for i in range(S)]]
    kc, vc, slot = _ring_cache(ksr[1], B, S, Hkv, hd, positions)
    qk, ks_ = quantize_kv(kc)
    qv, vs_ = quantize_kv(vc)
    pos_q = jnp.broadcast_to(live - C + jnp.arange(C)[None], (B, C))
    o = _pool_flash(q, qk, qv, pos_q, slot,
                    k_scale=jnp.swapaxes(ks_, 1, 2),
                    v_scale=jnp.swapaxes(vs_, 1, 2),
                    kv_limit=kvl, block_q=8, block_k=8)
    o_ref = A.chunked_attention(q, dequantize_kv(qk, ks_)[:, :kvl],
                                dequantize_kv(qv, vs_)[:, :kvl],
                                causal=True, pos_q=pos_q,
                                pos_kv=slot[:, :kvl], q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ===== block-size clamping (small/odd extents must not mis-grid) ============
def test_clamp_block_divisors():
    assert clamp_block(48, 512) == 48
    assert clamp_block(48, 32) == 24  # largest divisor <= request
    assert clamp_block(1, 128) == 1
    assert clamp_block(7, 4) == 1  # prime extent
    with pytest.raises(ValueError):
        clamp_block(0, 128)


@pytest.mark.parametrize("extent,kv_limit,block,want", [
    (2048, None, 512, (2048, 512)),
    (2048, 64, 512, (128, 128)),  # a short prefix still fills 128 lanes
    (2048, 200, 512, (256, 256)),
    (2048, 4096, 512, (2048, 512)),  # clipped to the ring
    (384, None, 256, (384, 128)),  # largest 128-multiple dividing 384
    (64, None, 512, (64, 64)),  # a truncated view: the whole extent
    (48, 40, 8, (48, 48)),  # not a 128-multiple: never a partial block
])
def test_kv_extent_lane_blocks(extent, kv_limit, block, want):
    assert kv_extent(extent, kv_limit, block) == want
    eff, blk = want
    assert eff % blk == 0 and (blk % 128 == 0 or blk == eff)


def test_decode_kernel_default_blocks_small_ring():
    """Ring smaller than the historical block_k=512 default."""
    B, Hq, Hkv, S, hd = 1, 4, 2, 48, 32
    kc, vc, slot = _ring_cache(
        jax.random.PRNGKey(18), B, S, Hkv, hd,
        np.broadcast_to(np.arange(S)[None], (B, S)))
    q = jax.random.normal(jax.random.PRNGKey(19), (B, Hq, hd), jnp.float32)
    cur = jnp.full((B,), S - 1, jnp.int32)
    o = ops.decode_attention(q, kc, vc, slot, cur)  # default block_k=512
    o_ref = A.decode_attention(q, kc, vc, slot, cur)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_default_blocks_small_prompt():
    """Prompt shorter than the historical block_q/block_k=128 defaults."""
    B, Hq, Hkv, S, hd = 1, 4, 2, 40, 32
    ksr = jax.random.split(jax.random.PRNGKey(20), 3)
    q = jax.random.normal(ksr[0], (B, Hq, S, hd), jnp.float32)
    k = jax.random.normal(ksr[1], (B, Hkv, S, hd), jnp.float32)
    v = jax.random.normal(ksr[2], (B, Hkv, S, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True)  # default 128 blocks
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
