"""Pallas TPU single-token decode attention over a ring-buffer KV cache.

The decode hot spot is memory-bound: each step streams the whole cache once.
Grid (B, Hkv, n_kv): all G query heads of one KV group are processed together
so the cache tile (block_k, hd) is read once per group, not once per query
head — the GQA bandwidth saving the cache layout exists for.  Online-softmax
state (m, l, acc) is VMEM scratch carried across kv tiles; slot validity
comes from the ``slot_pos`` ring-buffer positions (-1 = empty), which also
encodes causality and the sliding window.

Elastic dispatch (DESIGN.md §9) plugs in via ``kv_limit``: a static bound on
the live prefix shrinks the kv grid so the kernel only ever *addresses* the
first ``kv_limit`` ring slots of the full cache — the grid subsumes the
``truncate_rings`` view copy the XLA path needs.

Quantized pools (DESIGN.md §11) plug in via ``k_scale``/``v_scale``
(B, S, Hkv) f32: int8 cache tiles are read as is, and each key's scale is
applied to its score column and to its softmax weight before the context
matmul — algebraically the dequantized attention, and the HBM stream stays
1 byte/element.

Every per-slot operand (``slot_pos``, scales) is laid out with a singleton
second-to-last axis, ``(B, 1, S)`` / ``(B, Hkv, 1, S)``, so its blocks meet
the TPU (8, 128) tiling rule at every batch size; the kv block is a
multiple of 128 lanes or the whole ring (``blocks.kv_extent``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import kv_extent

NEG_INF = -1e30


def _decode_kernel(cur_pos_ref, q_ref, k_ref, v_ref, pos_ref, *rest,
                   block_k, n_kv, window, scale, G, quant):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, bk)
    if quant:
        s = s * ks_ref[0, 0]  # (1, bk) per-key scale

    cur = cur_pos_ref[pl.program_id(0)]  # this batch element's position
    slot = pos_ref[0]  # (1, bk) absolute positions of the cache slots
    ok = (slot >= 0) & (slot <= cur)
    if window is not None:
        ok &= slot > cur - window
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


def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *, window=None,
                     k_scale=None, v_scale=None, kv_limit=None,
                     block_k=512, interpret=False):
    """q: (B, Hq, hd); caches: (B, S, Hkv, hd); slot_pos: (B, S) int32;
    cur_pos: (B,) int32.  Returns (B, Hq, hd).

    ``kv_limit`` (static) restricts the kv grid to the first ``kv_limit``
    ring slots, rounded up to whole 128-lane blocks — the caller guarantees
    every live position sits below it, as in ``kvcache.truncate_rings``.
    ``k_scale``/``v_scale`` (B, S, Hkv) f32 mark an int8 cache and are
    applied in-kernel per tile.
    """
    B, Hq, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    S_eff, block_k = kv_extent(S, kv_limit, block_k)
    n_kv = S_eff // block_k
    scale = 1.0 / (hd ** 0.5)
    quant = k_scale is not None

    # layout: group q by kv head -> (B, Hkv, G, hd); caches head-major
    qg = q.reshape(B, Hkv, G, hd)
    kc = jnp.swapaxes(k_cache, 1, 2)  # (B, Hkv, S, hd)
    vc = jnp.swapaxes(v_cache, 1, 2)

    kernel = functools.partial(_decode_kernel, block_k=block_k, n_kv=n_kv,
                               window=window, scale=scale, G=G, quant=quant)
    grid = (B, Hkv, n_kv)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # cur_pos (B,) scalars
        pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, hd), lambda b, h, ki: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, ki: (b, 0, ki)),
    ]
    inputs = [cur_pos.astype(jnp.int32), qg, kc, vc,
              slot_pos.astype(jnp.int32)[:, None, :]]  # (B, 1, S)
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, block_k),
                                  lambda b, h, ki: (b, h, 0, ki))] * 2
        inputs += [jnp.swapaxes(sc, 1, 2)[:, :, None, :].astype(jnp.float32)
                   for sc in (k_scale, v_scale)]  # (B, Hkv, 1, S)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(*inputs)
    return out.reshape(B, Hq, hd)
