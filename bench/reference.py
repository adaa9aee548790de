"""Plain reference forward for dense GQA decoders, kept with the benchmark.

A copy of the program's ``models/reference.py`` (the same equations, written
with ``jax.numpy`` only: no KV cache, ring, slot pool or Pallas), so that no
later change to the program can move what served tokens are compared with.
Two changes make it fit on one chip at published widths, and neither
changes the arithmetic of a row: queries are taken in blocks (each row's
softmax sees every key, as before), and the output head is applied in
vocabulary blocks, reduced on the fly to what the comparison needs.

``operand_dtype`` rounds both operands of every matrix product through a
lower precision with a per-tensor scale (float8_e4m3fn: the control of
``bench/check.py``); ``None`` is the reference itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rounder(operand_dtype, compute_dtype):
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
    """Final-norm hidden states (B, S, d) of every position of ``tokens``."""
    B, S = tokens.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.norm_eps
    rnd = _rounder(operand_dtype, compute_dtype)

    def c(w):
        return w.astype(compute_dtype)

    def mm(a, w):
        return rnd(a) @ rnd(c(w))

    def norm(x, w):
        x32 = x.astype(jnp.float32)
        x32 = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (x32 * w.astype(jnp.float32)).astype(compute_dtype)

    # rotate-half RoPE: dims [i, i + hd/2] rotate by pos * theta^(-2i/hd)
    inv = cfg.rope_theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(t):
        t = t.astype(jnp.float32)
        a, b = t[..., :hd // 2], t[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               -1).astype(compute_dtype)

    qb = min(q_block, S)
    nqb = -(-S // qb)

    def attend(q, k, v):
        """Causal softmax attention, ``qb`` query rows at a time."""
        pad = nqb * qb - S
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qp = qp.reshape(B, nqb, qb, Hq, hd).swapaxes(0, 1)
        kpos = jnp.arange(S)

        def block(args):
            i, qi = args
            qpos = i * qb + jnp.arange(qb)
            s = jnp.einsum("bqhd,bkhd->bhqk", rnd(qi), rnd(k)
                           ).astype(jnp.float32)
            causal = kpos[None, :] <= qpos[:, None]
            s = jnp.where(causal, s / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v))

        o = jax.lax.map(block, (jnp.arange(nqb), qp))
        return o.swapaxes(0, 1).reshape(B, nqb * qb, Hq, hd)[:, :S]

    def layer(x, lp):
        ap, fp = lp["attn"], lp["ffn"]
        h = norm(x, lp["norm1"]["w"])
        q, k, v = mm(h, ap["wq"]), mm(h, ap["wk"]), mm(h, ap["wv"])
        if cfg.qkv_bias:
            q, k, v = q + c(ap["bq"]), k + c(ap["bk"]), v + c(ap["bv"])
        q = rope(q.reshape(B, S, Hq, hd))
        k = jnp.repeat(rope(k.reshape(B, S, Hkv, hd)), Hq // Hkv, axis=2)
        v = jnp.repeat(v.reshape(B, S, Hkv, hd), Hq // Hkv, axis=2)
        o = attend(q, k, v).reshape(B, S, Hq * hd)
        x = x + mm(o, ap["wo"])
        h = norm(x, lp["norm2"]["w"])
        if cfg.mlp_gated:
            f = jax.nn.silu(mm(h, fp["wg"])) * mm(h, fp["w1"])
        else:
            f = jax.nn.gelu(mm(h, fp["w1"]))
        return x + mm(f, fp["w2"]), None

    embed = params["embed"]["w"]
    x = c(jnp.take(embed, tokens, axis=0))
    x, _ = jax.lax.scan(layer, x, params["blocks"]["0"])
    return norm(x, params["final_norm"]["w"])


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
    rnd = _rounder(operand_dtype, compute_dtype)
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
    rnd = _rounder(operand_dtype, compute_dtype)
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
