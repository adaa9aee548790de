"""Pallas TPU RG-LRU linear recurrence (Griffin / RecurrentGemma).

The recurrence h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t) is
elementwise over the width axis, so the TPU-native layout tiles width into
VPU-aligned (block_w) lanes and walks the sequence in chunks; the running
state h is a (block_w,) VMEM scratch vector carried across the sequential
chunk dimension, and the inner chunk walk is a fori_loop over rows already
resident in VMEM (no HBM round-trips inside a chunk).

Grid: (B, n_w, n_chunks) with chunks "arbitrary" (state carry).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_log_ref, gate_ref, h0_ref, o_ref, hout_ref,
                  h_ref, *, chunk, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (C, Wb)
    a_log = a_log_ref[0].astype(jnp.float32)
    gate = gate_ref[0].astype(jnp.float32)
    a = jnp.exp(a_log)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0))
    b = beta * gate * x  # (C, Wb)

    def row(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = out.at[t].set(h)
        return h, out

    h0 = h_ref[...]
    out0 = jnp.zeros_like(x)
    h_fin, out = jax.lax.fori_loop(0, chunk, row, (h0, out0))
    h_ref[...] = h_fin
    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


def rglru_scan(x, a_log, gate, h0, *, chunk=128, block_w=512,
               interpret=False):
    """x/a_log/gate: (B, S, W); h0: (B, W) f32.

    Returns (h_seq (B, S, W) in x.dtype, h_final (B, W) f32)."""
    B, S, W = x.shape
    chunk = min(chunk, S)
    block_w = min(block_w, W)
    assert S % chunk == 0 and W % block_w == 0, (S, W, chunk, block_w)
    n_chunks = S // chunk
    n_w = W // block_w

    kernel = functools.partial(_rglru_kernel, chunk=chunk, n_chunks=n_chunks)
    grid = (B, n_w, n_chunks)
    out, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, block_w), lambda b, w, c: (b, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, block_w), lambda b, w, c: (b, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(x, a_log, gate, h0)
    return out, h_fin
