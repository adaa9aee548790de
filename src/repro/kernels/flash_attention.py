"""Pallas TPU flash attention (prefill): grouped-GQA, causal, sliding window.

TPU adaptation of the paper's chunked-prefill kernel class: q is tiled into
``block_q`` rows held in VMEM, k/v stream through VMEM in ``block_k`` tiles,
and the online-softmax state (m, l, acc) lives in VMEM scratch so HBM traffic
is O(S) per tile instead of O(S^2).  The MXU sees (block_q x hd) @
(hd x block_k) matmuls with hardware-aligned tiles (multiples of 128 when the
head dim allows).

Grid: (B, Hq, n_q, n_kv) with the kv dimension innermost ("arbitrary"
semantics — it carries the accumulator).  GQA is native: the k/v index map
sends query head h to kv head h // group_size, so KV is never materialized
per query head (unlike the XLA fallback path, which expands KV).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import clamp_block, kv_extent, lane_block

NEG_INF = -1e30


def _flash_kernel(pos_base_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_q, block_k, n_kv,
                  causal, window, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = pos_base_ref[0] + qi * block_q
    k_start = pos_base_ref[0] + ki * block_k

    # skip fully-masked blocks (strictly above the diagonal / out of window)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= pos_k <= pos_q
        if window is not None:
            ok &= pos_k > pos_q - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, pos_base=0,
                    block_q=128, block_k=128, interpret=False):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd).  Returns (B, Hq, Sq, hd).

    ``pos_base`` offsets absolute positions (chunked prefill against a cache
    whose first slot is position pos_base).
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = clamp_block(Sq, block_q)
    block_k = clamp_block(Skv, block_k)
    n_q = Sq // block_q
    n_kv = Skv // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_kv=n_kv,
        causal=causal, window=window, scale=scale)

    grid = (B, Hq, n_q, n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # pos_base scalar
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(jnp.asarray([pos_base], jnp.int32), q, k, v)
    return out


def _flash_pool_kernel(pos_q_ref, pos_kv_ref, q_ref, k_ref, v_ref, *rest,
                       n_kv, window, scale, quant):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    if quant:
        s = s * ks_ref[0, 0]  # (1, bk) per-key scale
    pq = pos_q_ref[0]   # (bq, 1) absolute positions of the chunk
    pk = pos_kv_ref[0]  # (1, bk) ring-slot positions (-1 = empty)
    ok = (pk >= 0) & (pk <= pq)
    if window is not None:
        ok &= pk > pq - window
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    pv = p * vs_ref[0, 0] if quant else p
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_pool(q, k, v, pos_q, pos_kv, *, window=None,
                         k_scale=None, v_scale=None, kv_limit=None,
                         block_q=128, block_k=128, interpret=False):
    """Chunked prefill over pool ring rows (in-pool prefill, DESIGN.md §7).

    q: (B, Hq, Sq, hd) — the current chunk's queries;
    k/v: (B, Hkv, Skv, hd) — the row's ring buffer (chunk K/V already
    written); pos_q: (B, Sq) and pos_kv: (B, Skv) int32 absolute positions
    (-1 = empty slot).  Causality, ring validity and the sliding window all
    come from the position arrays — exactly the mask
    ``models.attention.chunked_attention`` applies — so ring wrap-around and
    masked prefix-cache overhangs need no special cases.  Unlike the
    contiguous ``flash_attention`` above, kv tiles cannot be skipped by
    block-range tests (slot order is not position order); every tile is
    scored and masking does the rest.

    ``kv_limit`` (static) restricts the kv grid to the first ``kv_limit``
    ring slots, rounded up to whole 128-lane blocks; ``k_scale``/``v_scale``
    (B, Hkv, Skv) f32 mark an int8 ring and dequantize in-kernel.  The
    position and scale operands get singleton axes so every block meets the
    TPU (8, 128) tiling rule (see ``decode_attention``).  Returns
    (B, Hq, Sq, hd).
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    Skv_eff, block_k = kv_extent(Skv, kv_limit, block_k)
    block_q = lane_block(Sq, block_q)
    n_q = Sq // block_q
    n_kv = Skv_eff // block_k
    scale = 1.0 / (hd ** 0.5)
    quant = k_scale is not None

    kernel = functools.partial(_flash_pool_kernel, n_kv=n_kv, window=window,
                               scale=scale, quant=quant)
    grid = (B, Hq, n_q, n_kv)
    in_specs = [
        pl.BlockSpec((1, block_q, 1), lambda b, h, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, qi, ki: (b, 0, ki)),
        pl.BlockSpec((1, 1, block_q, hd),
                     lambda b, h, qi, ki: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, hd),
                     lambda b, h, qi, ki: (b, h // G, ki, 0)),
        pl.BlockSpec((1, 1, block_k, hd),
                     lambda b, h, qi, ki: (b, h // G, ki, 0)),
    ]
    inputs = [pos_q.astype(jnp.int32)[:, :, None],   # (B, Sq, 1) column
              pos_kv.astype(jnp.int32)[:, None, :],  # (B, 1, Skv) row
              q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, block_k),
                                  lambda b, h, qi, ki: (b, h // G, 0, ki))] * 2
        inputs += [sc.astype(jnp.float32)[:, :, None, :]
                   for sc in (k_scale, v_scale)]  # (B, Hkv, 1, Skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_pool",
    )(*inputs)
    return out

