"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults from the platform: compiled Mosaic kernels on
``tpu``; interpret mode (the kernel body run per grid step on the host,
which checks the TPU dataflow) on ``cpu``, where the tests run; any other
platform is an error rather than a silent interpreter.  The model reaches
these through ``kernel_backend="pallas"`` (transformer.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import (decode_attention as _dec, flash_attention as _fa,
                           moe_gemm as _mg, rglru_scan as _rg,
                           rwkv6_scan as _rk)


def _interpret_default() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU (and are interpreted on "
            f"CPU); platform {platform!r} has neither path")
    return platform == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, pos_base=0,
                    block_q=128, block_k=128, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               pos_base=pos_base, block_q=block_q,
                               block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "kv_limit", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pool(q, k, v, pos_q, pos_kv, *, window=None,
                         k_scale=None, v_scale=None, kv_limit=None,
                         block_q=128, block_k=128, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _fa.flash_attention_pool(q, k, v, pos_q, pos_kv, window=window,
                                    k_scale=k_scale, v_scale=v_scale,
                                    kv_limit=kv_limit, block_q=block_q,
                                    block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "kv_limit", "block_k",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *, window=None,
                     k_scale=None, v_scale=None, kv_limit=None,
                     block_k=512, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _dec.decode_attention(q, k_cache, v_cache, slot_pos, cur_pos,
                                 window=window, k_scale=k_scale,
                                 v_scale=v_scale, kv_limit=kv_limit,
                                 block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w_log, u, *, chunk=32, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _rk.rwkv6_scan(r, k, v, w_log, u, chunk=chunk,
                          interpret=interpret)


def rwkv6_apply(r, k, v, w_log, u, state0, *, chunk=32, interpret=None):
    """Continuation-aware WKV6: folds a nonzero initial state in by exact
    linearity (out += (r * e^{L_prev}) @ state0 decayed), then runs the
    zero-state kernel."""
    out, s_fin = rwkv6_scan(r, k, v, w_log, u, chunk=chunk,
                            interpret=interpret)
    L = jnp.cumsum(w_log.astype(jnp.float32), axis=1)
    L_prev = L - w_log.astype(jnp.float32)
    extra = jnp.einsum("bsd,bde->bse", r.astype(jnp.float32)
                       * jnp.exp(L_prev), state0)
    s_fin = s_fin + state0 * jnp.exp(L[:, -1, :])[:, :, None]
    return (out + extra.astype(out.dtype)), s_fin


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_scan(x, a_log, gate, h0, *, chunk=128, block_w=512,
               interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _rg.rglru_scan(x, a_log, gate, h0, chunk=chunk, block_w=block_w,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def moe_gemm(x, w, *, block_c=128, block_f=128, block_d=512, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    return _mg.moe_gemm(x, w, block_c=block_c, block_f=block_f,
                        block_d=block_d, interpret=interpret)
