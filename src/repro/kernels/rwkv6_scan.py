"""Pallas TPU chunked WKV6 scan (RWKV-6 "Finch" recurrence).

TPU adaptation: the GPU reference implementations thread one warp per
(batch, head); here each grid step owns a (chunk x head_dim) tile in VMEM and
the (D x D) recurrent state lives in VMEM scratch, carried across the
sequential chunk dimension.  Intra-chunk work is the stable pairwise
log-space form (ratios exp(L[t-1]-L[s]) <= 1 for s < t), expressed as MXU
matmuls over (C, D) tiles; cross-chunk state update is one (D, C) @ (C, D)
matmul.

Grid: (B*H, n_chunks) — chunks are "arbitrary" (carry the state scratch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref,
                 state_ref, *, chunk, head_dim, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w_log = w_ref[0].astype(jnp.float32)  # log decay, < 0
    u = u_ref[0].astype(jnp.float32)  # (1, D) bonus
    S0 = state_ref[...]  # (D, D) k-dim x v-dim

    L = jnp.cumsum(w_log, axis=0)  # inclusive
    L_prev = L - w_log

    # inter-chunk: (r * e^{L_prev}) @ S0
    r_dec = r * jnp.exp(L_prev)
    o = jnp.dot(r_dec, S0, preferred_element_type=jnp.float32)

    # intra-chunk pairwise: P[t,s] = sum_d r[t,d] k[s,d] e^{L[t-1,d]-L[s,d]}
    ratio = jnp.exp(L_prev[:, None, :] - L[None, :, :])  # (C, C, D) <= 1
    P = jnp.einsum("td,sd,tsd->ts", r, k, ratio)
    C = chunk
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    P = jnp.where(s_idx < t_idx, P, 0.0)
    diag = jnp.sum(r * k * u, axis=1)  # (C,) bonus at s == t
    P = P + jnp.where(s_idx == t_idx, diag[:, None], 0.0)
    o = o + jnp.dot(P, v, preferred_element_type=jnp.float32)

    # state update: S = diag(e^{L_C}) S0 + sum_s (k_s e^{L_C - L_s}) v_s^T
    decay_all = jnp.exp(L[-1:, :])  # (1, D)
    k_dec = k * jnp.exp(L[-1:, :] - L)  # (C, D), ratios <= 1
    state_ref[...] = S0 * decay_all.T + jnp.dot(
        k_dec.T, v, preferred_element_type=jnp.float32)

    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_out_ref[0] = state_ref[...]


def rwkv6_scan(r, k, v, w_log, u, *, chunk=32, interpret=False):
    """r/k/v/w_log: (BH, S, D); u: (BH, 1, D) broadcast bonus.

    Returns (out (BH, S, D) in r.dtype, final_state (BH, D, D) f32).
    State starts at zero (engine-level chunk continuation passes state via a
    dedicated first chunk fold; see ops.rwkv6_apply).
    """
    BH, S, D = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, head_dim=D,
                               n_chunks=n_chunks)
    grid = (BH, n_chunks)
    out, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, D), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, D), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, D, D), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv6_scan",
    )(r, k, v, w_log, u)
    return out, s_out
