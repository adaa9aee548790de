"""Pallas TPU grouped expert GEMM: (E, C, d) @ (E, d, f) -> (E, C, f).

The MoE hot spot after capacity dispatch.  Each grid step owns one
MXU-aligned (block_c x block_f) output tile of one expert and accumulates
over d in block_d slices held in VMEM — a batched matmul whose batch
dimension is the expert index, which is exactly the layout expert-parallel
sharding decomposes over.

Grid: (E, C/bc, f/bf, d/bd) with d innermost ("arbitrary": carries acc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_d):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bc, bd)
    w = w_ref[0]  # (bd, bf)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _emit():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm(x, w, *, block_c=128, block_f=128, block_d=512,
             interpret=False):
    """x: (E, C, d); w: (E, d, f).  Returns (E, C, f) in x.dtype."""
    E, C, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert C % block_c == 0 and f % block_f == 0 and d % block_d == 0
    n_d = d // block_d

    kernel = functools.partial(_moe_gemm_kernel, n_d=n_d)
    grid = (E, C // block_c, f // block_f, n_d)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ci, fi, di: (e, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="moe_gemm",
    )(x, w)
    return out
