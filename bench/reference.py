"""Plain reference forward, kept with the benchmark.

The trunk up to the final norm is the configuration's family's
(``bench/family.py``: the same equations as the program's, written with
``jax.numpy`` only, so that no later change to the program can move what
served tokens are compared with).  Every family ends in a matrix over the
vocabulary, applied here in vocabulary blocks and reduced on the fly to
what the comparison needs; that changes no row's arithmetic and lets the
reference fit on one chip at published widths.

``operand_dtype`` rounds both operands of every matrix product through a
lower precision with a per-tensor scale (float8_e4m3fn: the control of
``bench/check.py``); ``None`` is the reference itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import family


def rounder(operand_dtype, compute_dtype):
    if operand_dtype is None:
        return lambda a: a
    top = float(jnp.finfo(operand_dtype).max)

    def rnd(a):
        a = a.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return ((a / scale).astype(operand_dtype).astype(jnp.float32)
                * scale).astype(compute_dtype)
    return rnd


def reference_hidden(cfg, params, tokens, compute_dtype=jnp.float32, *,
                     q_block=512, operand_dtype=None):
    """Final-norm hidden states (B, S, d) of every position of ``tokens``,
    by the configuration's family."""
    return family.of(cfg).reference_hidden(
        cfg, params, tokens, compute_dtype, q_block=q_block,
        operand_dtype=operand_dtype)


def _head_matrix(params):
    """(d, V) output projection; the embedding transposed when tied."""
    if "lm_head" in params:
        return params["lm_head"]["w"], False
    return params["embed"]["w"], True


def reference_logits(cfg, params, tokens, compute_dtype=jnp.float32, *,
                     q_block=512, operand_dtype=None):
    """Logits (B, S, vocab) of every position of ``tokens`` (B, S)."""
    x = reference_hidden(cfg, params, tokens, compute_dtype,
                         q_block=q_block, operand_dtype=operand_dtype)
    rnd = rounder(operand_dtype, compute_dtype)
    w, tied = _head_matrix(params)
    w = rnd(w.astype(compute_dtype))
    if tied:
        return jnp.einsum("bsd,vd->bsv", rnd(x), w).astype(jnp.float32)
    return (rnd(x) @ w).astype(jnp.float32)


def head_summary(params, h, targets, compute_dtype=jnp.float32, *,
                 v_block=16384, operand_dtype=None):
    """For hidden rows ``h`` (N, d): the largest logit, its token, and the
    logit of ``targets`` (N,), with the head applied ``v_block`` vocabulary
    rows at a time (no (N, vocab) array is ever made)."""
    rnd = rounder(operand_dtype, compute_dtype)
    w, tied = _head_matrix(params)
    V = w.shape[0] if tied else w.shape[1]
    hx = rnd(h.astype(compute_dtype))
    nvb = -(-V // v_block)
    # a per-tensor scale must be the whole matrix's, as in reference_logits
    scale_src = None if operand_dtype is None else w

    def block_logits(i):
        idx = jnp.minimum(i * v_block + jnp.arange(v_block), V - 1)
        wb = jnp.take(w, idx, axis=0 if tied else 1).astype(compute_dtype)
        if scale_src is not None:
            wb = _round_with(wb, scale_src, operand_dtype, compute_dtype)
        z = (hx @ (wb.T if tied else wb)).astype(jnp.float32)
        valid = (i * v_block + jnp.arange(v_block)) < V
        return jnp.where(valid[None, :], z, -jnp.inf), idx

    def step(carry, i):
        best, arg = carry
        z, idx = block_logits(i)
        j = jnp.argmax(z, axis=-1)
        zb = jnp.max(z, axis=-1)
        take = zb > best
        return (jnp.where(take, zb, best),
                jnp.where(take, idx[j], arg)), None

    n = h.shape[0]
    init = (jnp.full((n,), -jnp.inf, jnp.float32), jnp.zeros((n,), jnp.int32))
    (best, arg), _ = jax.lax.scan(step, init, jnp.arange(nvb))
    wt = jnp.take(w, targets, axis=0 if tied else 1).astype(compute_dtype)
    if scale_src is not None:
        wt = _round_with(wt, scale_src, operand_dtype, compute_dtype)
    tgt = jnp.sum(hx.astype(jnp.float32)
                  * (wt if tied else wt.T).astype(jnp.float32), -1)
    return best, arg, tgt


def _round_with(a, whole, operand_dtype, compute_dtype):
    """Round ``a`` through ``operand_dtype`` with the scale of ``whole``."""
    top = float(jnp.finfo(operand_dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(whole.astype(jnp.float32))),
                        1e-30) / top
    return ((a.astype(jnp.float32) / scale).astype(operand_dtype)
            .astype(jnp.float32) * scale).astype(compute_dtype)
