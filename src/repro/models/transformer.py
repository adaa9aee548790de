"""Generic multi-family transformer: init, train forward, prefill/extend, decode.

One code path (`extend`) covers chunked prefill (C tokens against an existing
cache — the paper's elastic chunked kernel), full prefill (cache fresh), and
decode (C == 1).  Training uses a cacheless `forward`.

Layer layout (mirrors params/cache pytrees):
  head   — `first_k_dense_layers` unrolled layers (distinct d_ff),
  blocks — the repeated `layer_pattern` executed under jax.lax.scan,
  tail   — `tail_pattern` unrolled layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import kvcache
from repro.models import attention as A
from repro.models.attention import (chunked_attention, decode_attention,
                                    full_attention)
from repro.models.layers import apply_rope, embed_tokens, lm_logits, mlp, rms_norm
from repro.models.moe import moe_ffn
from repro.models.ssm import rglru_block, rwkv6_block


# ============================ layout helpers ================================
def layout(cfg):
    """(head_kinds, pattern, repeats, tail_kinds)."""
    head = tuple("attn" for _ in range(cfg.first_k_dense_layers))
    if cfg.layer_pattern:
        pattern, repeats, tail = (tuple(cfg.layer_pattern), cfg.pattern_repeats,
                                  tuple(cfg.tail_pattern))
    else:
        kind = cfg.block_kind(cfg.first_k_dense_layers) \
            if cfg.num_layers > cfg.first_k_dense_layers else "attn"
        pattern = (kind,)
        repeats = cfg.num_layers - len(head)
        tail = ()
    assert len(head) + len(pattern) * repeats + len(tail) == cfg.num_layers, \
        (cfg.name, len(head), pattern, repeats, tail)
    return head, pattern, repeats, tail


# ============================ initialization ================================
def _init_attn(cfg, key, dtype, cross: bool):
    k = iter(jax.random.split(key, 16))
    d = cfg.d_model
    std = 0.02
    out_std = 0.02 / (2 * cfg.num_layers) ** 0.5
    nrm = lambda k_, sh, s=std: (jax.random.normal(k_, sh) * s).astype(dtype)
    if cfg.use_mla:
        dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
        H = cfg.num_heads
        p = {
            "w_q": nrm(next(k), (d, H * (dn + dr))),
            "w_dkv": nrm(next(k), (d, r)),
            "w_krope": nrm(next(k), (d, dr)),
            "w_uk": nrm(next(k), (r, H, dn)),
            "w_uv": nrm(next(k), (r, H, dv)),
            "wo": nrm(next(k), (H * dv, d), out_std),
            "kv_norm": jnp.ones((r,), dtype),
        }
    else:
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        p = {
            "wq": nrm(next(k), (d, Hq * hd)),
            "wk": nrm(next(k), (d, Hkv * hd)),
            "wv": nrm(next(k), (d, Hkv * hd)),
            "wo": nrm(next(k), (Hq * hd, d), out_std),
        }
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((Hq * hd,), dtype)
            p["bk"] = jnp.zeros((Hkv * hd,), dtype)
            p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    if cross:
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        p["xq"] = nrm(next(k), (d, Hq * hd))
        p["xk"] = nrm(next(k), (d, Hkv * hd))
        p["xv"] = nrm(next(k), (d, Hkv * hd))
        p["xo"] = nrm(next(k), (Hq * hd, d), out_std)
    return p


def _init_ffn(cfg, key, dtype, d_ff):
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    std = 0.02
    out_std = 0.02 / (2 * cfg.num_layers) ** 0.5
    p = {"w1": (jax.random.normal(k1, (d, d_ff)) * std).astype(dtype),
         "w2": (jax.random.normal(k2, (d_ff, d)) * out_std).astype(dtype)}
    if cfg.mlp_gated:
        p["wg"] = (jax.random.normal(k3, (d, d_ff)) * std).astype(dtype)
    return p


def _init_moe(cfg, key, dtype):
    kr, ke, ks = jax.random.split(key, 3)
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    std = 0.02
    out_std = 0.02 / (2 * cfg.num_layers) ** 0.5
    ekeys = jax.random.split(ke, 3)
    experts = {
        "w1": (jax.random.normal(ekeys[0], (E, d, f)) * std).astype(dtype),
        "w2": (jax.random.normal(ekeys[1], (E, f, d)) * out_std).astype(dtype),
    }
    if cfg.mlp_gated:
        experts["wg"] = (jax.random.normal(ekeys[2], (E, d, f)) * std).astype(dtype)
    p = {"router": (jax.random.normal(kr, (d, E)) * std).astype(jnp.float32),
         "experts": experts}
    if cfg.num_shared_experts:
        p["shared"] = _init_ffn(cfg, ks, dtype,
                                cfg.num_shared_experts * cfg.moe_d_ff)
    return p


def _init_rwkv6(cfg, key, dtype):
    k = iter(jax.random.split(key, 12))
    d = cfg.d_model
    D = cfg.ssm_head_dim
    H = d // D
    std = 0.02
    out_std = 0.02 / (2 * cfg.num_layers) ** 0.5
    lora_r = 64
    nrm = lambda k_, sh, s=std: (jax.random.normal(k_, sh) * s).astype(dtype)
    p = {
        "wr": nrm(next(k), (d, d)), "wk": nrm(next(k), (d, d)),
        "wv": nrm(next(k), (d, d)), "wg": nrm(next(k), (d, d)),
        "wo": nrm(next(k), (d, d), out_std),
        "w0": (jnp.zeros((d,)) + 0.5).astype(jnp.float32),  # base decay ~ e^{-e^{0.5}}
        "w_lora_a": nrm(next(k), (d, lora_r)),
        "w_lora_b": nrm(next(k), (lora_r, d)),
        "u": nrm(next(k), (H, D)),
        "ln_w": jnp.ones((d,), dtype), "ln_b": jnp.zeros((d,), dtype),
    }
    for n in ("r", "k", "v", "g", "w"):
        p[f"mu_{n}"] = (jnp.full((d,), 0.5)).astype(dtype)
    # channel mix (RWKV FFN uses its own token shift; handled in block fn)
    p["cm"] = {
        "mu": (jnp.full((d,), 0.5)).astype(dtype),
        "wk_cm": nrm(next(k), (d, cfg.d_ff)),
        "wv_cm": nrm(next(k), (cfg.d_ff, d), out_std),
        "wr_cm": nrm(next(k), (d, d)),
    }
    return p


def _init_rglru(cfg, key, dtype):
    k = iter(jax.random.split(key, 8))
    d, W, cw = cfg.d_model, cfg.lru_width, cfg.conv1d_width
    std = 0.02
    out_std = 0.02 / (2 * cfg.num_layers) ** 0.5
    nrm = lambda k_, sh, s=std: (jax.random.normal(k_, sh) * s).astype(dtype)
    return {
        "w_x": nrm(next(k), (d, W)), "w_gate": nrm(next(k), (d, W)),
        "conv_w": nrm(next(k), (cw, W)), "conv_b": jnp.zeros((W,), dtype),
        "w_a": nrm(next(k), (W, W)), "b_a": jnp.zeros((W,), dtype),
        "w_i": nrm(next(k), (W, W)), "b_i": jnp.zeros((W,), dtype),
        # softplus(lam) ~ 0.7 -> decay exp(-8*0.7*sigmoid) moderately strong
        "lam": jnp.full((W,), 0.2, jnp.float32),
        "w_out": nrm(next(k), (W, d), out_std),
    }


def init_layer(cfg, kind: str, key, dtype, *, layer_idx: int, cross: bool):
    kn, km, kf = jax.random.split(key, 3)
    d = cfg.d_model
    p = {"norm1": {"w": jnp.ones((d,), dtype)},
         "norm2": {"w": jnp.ones((d,), dtype)}}
    if kind == "attn":
        p["attn"] = _init_attn(cfg, km, dtype, cross)
        if cross:
            p["norm_x"] = {"w": jnp.ones((d,), dtype)}
    elif kind == "rwkv6":
        p["tm"] = _init_rwkv6(cfg, km, dtype)
    elif kind == "rglru":
        p["rg"] = _init_rglru(cfg, km, dtype)
    # FFN (rwkv6 carries its channel-mix inside tm["cm"])
    if kind != "rwkv6":
        if cfg.is_moe and layer_idx >= cfg.first_k_dense_layers:
            p["moe"] = _init_moe(cfg, kf, dtype)
        else:
            dff = cfg.dense_d_ff if (cfg.is_moe and
                                     layer_idx < cfg.first_k_dense_layers) \
                else cfg.d_ff
            p["ffn"] = _init_ffn(cfg, kf, dtype, dff)
    return p


def init_params(cfg, key, dtype=jnp.bfloat16):
    head, pattern, repeats, tail = layout(cfg)
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params = {
        "embed": {"w": (jax.random.normal(keys[0], (cfg.vocab_size, d))
                        * 0.02).astype(dtype)},
        "final_norm": {"w": jnp.ones((d,), dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": (jax.random.normal(keys[1], (d, cfg.vocab_size))
                                   * 0.02).astype(dtype)}
    cross = cfg.is_encoder_decoder
    # head layers (unrolled)
    hkeys = jax.random.split(keys[2], max(len(head), 1))
    params["head"] = tuple(
        init_layer(cfg, k_, hkeys[i], dtype, layer_idx=i, cross=cross)
        for i, k_ in enumerate(head))
    # scanned pattern groups: stacked over repeats via vmap
    base_idx = len(head)
    blocks = {}
    pkeys = jax.random.split(keys[3], max(len(pattern), 1))
    for pi, kind in enumerate(pattern):
        rkeys = jax.random.split(pkeys[pi], repeats)
        blocks[str(pi)] = jax.vmap(
            lambda kk: init_layer(cfg, kind, kk, dtype,
                                  layer_idx=base_idx + pi, cross=cross))(rkeys)
    params["blocks"] = blocks
    # tail layers (unrolled)
    tkeys = jax.random.split(keys[4], max(len(tail), 1))
    params["tail"] = tuple(
        init_layer(cfg, k_, tkeys[i], dtype,
                   layer_idx=cfg.num_layers - len(tail) + i, cross=cross)
        for i, k_ in enumerate(tail))
    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[5], cfg.num_encoder_layers)
        params["encoder"] = jax.vmap(
            lambda kk: init_layer(cfg, "attn", kk, dtype, layer_idx=0,
                                  cross=False))(ekeys)
    if cfg.frontend != "none" and cfg.frontend_dim != cfg.d_model:
        params["frontend_proj"] = {
            "w": (jax.random.normal(keys[6], (cfg.frontend_dim, d)) * 0.02
                  ).astype(dtype)}
    return params


# ============================ block application =============================
def _rwkv6_channel_mix(x_seq, p, shift_state):
    """RWKV channel-mix FFN with its own token shift.

    x_seq: (B,S,d).  Returns (y, new_shift)."""
    prev = jnp.concatenate([shift_state[:, None, :], x_seq[:, :-1, :]], axis=1)
    xk = x_seq + (prev - x_seq) * p["mu"]
    k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, p["wk_cm"])))
    kv = jnp.einsum("bsf,fd->bsd", k, p["wv_cm"])
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xk, p["wr_cm"]))
    return r * kv, x_seq[:, -1, :]


def _attn_mix_train(cfg, lp, x, ctx):
    """Cacheless causal self-attention over the full sequence (training)."""
    B, S, d = x.shape
    ap = lp["attn"]
    window = ctx.get("window") or cfg.sliding_window
    pos = ctx["positions"]  # (S,)
    if cfg.use_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        H = cfg.num_heads
        q = jnp.einsum("bsd,de->bse", x, ap["w_q"]).reshape(B, S, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
        c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, ap["w_dkv"]),
                        ap["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(jnp.einsum("bsd,dr->bsr", x, ap["w_krope"])[:, :, None, :],
                            pos, cfg.rope_theta)
        out = A.mla_prefill_attention(q_nope, q_rope, c_kv, k_rope, ap,
                                      pos_q=pos, pos_kv=pos, window=window,
                                      q_chunk=ctx["q_chunk"],
                                      kv_chunk=ctx["kv_chunk"])
        return jnp.einsum("bsD,Dd->bsd", out.reshape(B, S, H * dv), ap["wo"])
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, ap["wq"])
    k = jnp.einsum("bsd,de->bse", x, ap["wk"])
    v = jnp.einsum("bsd,de->bse", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = apply_rope(q.reshape(B, S, Hq, hd), pos, cfg.rope_theta)
    k = apply_rope(k.reshape(B, S, Hkv, hd), pos, cfg.rope_theta)
    v = v.reshape(B, S, Hkv, hd)
    if ctx.get("tp_axis"):
        k, v = _expand_kv(k, Hq // Hkv), _expand_kv(v, Hq // Hkv)
        q = _constrain_heads(q, ctx)
        k = _constrain_heads(k, ctx)
        v = _constrain_heads(v, ctx)
    out = chunked_attention(q, k, v, causal=True, window=window,
                            pos_q=pos, pos_kv=pos,
                            q_chunk=ctx["q_chunk"], kv_chunk=ctx["kv_chunk"])
    out = _constrain_heads(out, ctx)
    return jnp.einsum("bse,ed->bsd", out.reshape(B, S, Hq * hd), ap["wo"])


def _store_kv(st, vals, pos, ctx):
    """Write a chunk's ring values (K/V or latents, scales, positions) into
    one layer's state.  Returns ``(state, view)``: the state to carry on,
    and the ring window attention reads.

    A pooled decode step (``ctx["pool_write"]``, see :func:`_decode_block`)
    scatters into the carried pool in place and reads a window of it;
    otherwise the chunk tail (the last ``min(C, alloc)`` tokens) lands at
    ``pos % alloc`` of ``st`` itself, which attention then reads whole."""
    if "pool_write" in ctx:
        return ctx["pool_write"](st, vals, pos)
    B, C = pos.shape
    alloc = st["slot_pos"].shape[1]
    n = min(C, alloc)
    bidx = jnp.arange(B)[:, None]
    slots = pos[:, C - n:] % alloc
    st = dict(st, **{name: st[name].at[bidx, slots].set(v[:, C - n:])
                     for name, v in vals.items()})
    return st, st


def _attn_mix_extend(cfg, lp, x, st, ctx):
    """Self-attention of a C-token chunk against the ring-buffer cache.

    Writes the chunk's K/V into the cache first (:func:`_store_kv`), then
    attends with position masks; C == 1 uses the single-token decode
    kernels (incl. absorbed MLA).
    """
    B, C, d = x.shape
    ap = lp["attn"]
    window = ctx.get("window") or cfg.sliding_window
    pos = ctx["pos0"][:, None] + jnp.arange(C)[None, :]  # (B, C) absolute

    if cfg.use_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        H = cfg.num_heads
        q = jnp.einsum("bsd,de->bse", x, ap["w_q"]).reshape(B, C, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
        c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, ap["w_dkv"]),
                        ap["kv_norm"], cfg.norm_eps)
        k_rope = apply_rope(jnp.einsum("bsd,dr->bsr", x, ap["w_krope"])
                            [:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
        st, kv = _store_kv(st, {"c": c_kv, "kr": k_rope, "slot_pos": pos},
                           pos, ctx)
        if C == 1:
            c_r = _constrain_cache_seq(kv["c"], ctx)
            kr_r = _constrain_cache_seq(kv["kr"], ctx)
            sp_r = _constrain_cache_seq(kv["slot_pos"], ctx)
            out = A.mla_decode_attention(q_nope[:, 0], q_rope[:, 0], c_r,
                                         kr_r, ap, sp_r,
                                         pos[:, 0], window=window)[:, None]
        else:
            k_nope, vv = A.mla_expand_kv(kv["c"], ap)
            kr_b = jnp.broadcast_to(kv["kr"][:, :, None, :],
                                    (B, kv["kr"].shape[1], H, dr))
            qq = jnp.concatenate([q_nope, q_rope], -1)
            kk = jnp.concatenate([k_nope, kr_b], -1)
            if dv < dn + dr:
                vv = jnp.pad(vv, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
            out = chunked_attention(
                qq, kk, vv, causal=True, window=window, pos_q=pos,
                pos_kv=kv["slot_pos"], q_chunk=ctx["q_chunk"],
                kv_chunk=ctx["kv_chunk"])[..., :dv]
        y = jnp.einsum("bsD,Dd->bsd", out.reshape(B, C, H * dv), ap["wo"])
        return y, st

    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, ap["wq"])
    k = jnp.einsum("bsd,de->bse", x, ap["wk"])
    v = jnp.einsum("bsd,de->bse", x, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = apply_rope(q.reshape(B, C, Hq, hd), pos, cfg.rope_theta)
    k = apply_rope(k.reshape(B, C, Hkv, hd), pos, cfg.rope_theta)
    v = v.reshape(B, C, Hkv, hd)
    # int8 pool (DESIGN.md §11): quantize the chunk's K/V once at write time;
    # reads dequantize inside the same jitted program (XLA fuses the scale
    # multiply into the score/context matmul reads; the Pallas kernels take
    # the int8 ring + scales directly), so a quantized decode step stays ONE
    # device program per (rows, kv_limit) bucket.
    quant = "k_scale" in st
    if quant:
        qk, ks = kvcache.quantize_kv(k)
        qv, vs = kvcache.quantize_kv(v)
        vals = {"k": qk, "v": qv, "k_scale": ks, "v_scale": vs}
    else:
        vals = {"k": k, "v": v}
    st, kv = _store_kv(st, dict(vals, slot_pos=pos), pos, ctx)
    # Pallas hot path (kernel_backend="pallas"): same mask semantics as the
    # XLA reference, GQA done natively in-kernel.  The kernels are
    # single-device, so a sharded run must ask for the XLA path.
    pallas = ctx.get("kernel_backend") == "pallas"
    if pallas and ctx.get("tp_axis"):
        raise NotImplementedError(
            "kernel_backend='pallas' is single-device; a tp_axis-sharded "
            "run needs kernel_backend='xla'")
    if C == 1:
        if pallas:
            from repro.kernels import ops as kops
            out = kops.decode_attention(
                q[:, 0], kv["k"], kv["v"], kv["slot_pos"], pos[:, 0],
                window=window, k_scale=kv.get("k_scale"),
                v_scale=kv.get("v_scale"))[:, None]
        else:
            k_r = _constrain_cache_seq(kv["k"], ctx)
            v_r = _constrain_cache_seq(kv["v"], ctx)
            sp_r = _constrain_cache_seq(kv["slot_pos"], ctx)
            if quant:
                k_r = kvcache.dequantize_kv(k_r, kv["k_scale"], k.dtype)
                v_r = kvcache.dequantize_kv(v_r, kv["v_scale"], v.dtype)
            out = decode_attention(q[:, 0], k_r, v_r, sp_r,
                                   pos[:, 0], window=window)[:, None]
    elif pallas:
        from repro.kernels import ops as kops
        out = kops.flash_attention_pool(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(kv["k"], 1, 2),
            jnp.swapaxes(kv["v"], 1, 2), pos, kv["slot_pos"], window=window,
            k_scale=jnp.swapaxes(kv["k_scale"], 1, 2) if quant else None,
            v_scale=jnp.swapaxes(kv["v_scale"], 1, 2) if quant else None)
        out = jnp.swapaxes(out, 1, 2)
    else:
        kk, vv = kv["k"], kv["v"]
        if quant:
            kk = kvcache.dequantize_kv(kk, kv["k_scale"], k.dtype)
            vv = kvcache.dequantize_kv(vv, kv["v_scale"], v.dtype)
        if ctx.get("tp_axis"):
            kk, vv = _expand_kv(kk, Hq // Hkv), _expand_kv(vv, Hq // Hkv)
            q = _constrain_heads(q, ctx)
            kk = _constrain_heads(kk, ctx)
            vv = _constrain_heads(vv, ctx)
        out = chunked_attention(q, kk, vv, causal=True, window=window,
                                pos_q=pos, pos_kv=kv["slot_pos"],
                                q_chunk=ctx["q_chunk"], kv_chunk=ctx["kv_chunk"])
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, C, Hq * hd), ap["wo"])
    return y, st


def _cross_attn(cfg, lp, x, st, ctx):
    """Encoder-decoder cross attention; K/V cached in state (or from enc_out)."""
    B, C, d = x.shape
    ap = lp["attn"]
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, ap["xq"]).reshape(B, C, Hq, hd)
    if st is not None and "xk" in st:
        xk, xv = st["xk"], st["xv"]
    else:
        enc = ctx["enc_out"]
        xk = jnp.einsum("bfd,de->bfe", enc, ap["xk"]).reshape(
            B, enc.shape[1], Hkv, hd)
        xv = jnp.einsum("bfd,de->bfe", enc, ap["xv"]).reshape(
            B, enc.shape[1], Hkv, hd)
    out = full_attention(q, xk, xv, causal=False)
    return jnp.einsum("bse,ed->bsd", out.reshape(B, C, Hq * hd), ap["xo"])


def _ffn_apply(cfg, lp, x, ctx):
    """FFN / MoE sublayer on (B,S,d); returns (y, aux_loss)."""
    if "moe" in lp:
        B, S, d = x.shape
        # decode is dropless (capacity = T); prefill/train use capacity factor
        cap = B * S if ctx["mode"] == "decode" else 0
        y, aux = moe_ffn(x.reshape(B * S, d), lp["moe"], cfg,
                         capacity_factor=ctx.get("capacity_factor", 1.25),
                         capacity_override=cap)
        return y.reshape(B, S, d), aux
    return mlp(x, lp["ffn"], cfg.mlp_gated), jnp.zeros((), jnp.float32)


def apply_block(cfg, kind, lp, x, st, ctx):
    """One residual block.  st is None in training mode.

    Returns (x, new_state, aux_loss).
    """
    aux = jnp.zeros((), jnp.float32)
    mode = ctx["mode"]
    if kind == "attn":
        h = rms_norm(x, lp["norm1"]["w"], cfg.norm_eps)
        if mode == "train":
            y = _attn_mix_train(cfg, lp, h, ctx)
            new_st = st
        else:
            y, new_st = _attn_mix_extend(cfg, lp, h, st, ctx)
        x = x + y
        if cfg.is_encoder_decoder and "norm_x" in lp:
            hx = rms_norm(x, lp["norm_x"]["w"], cfg.norm_eps)
            x = x + _cross_attn(cfg, lp, hx, st, ctx)
        h2 = rms_norm(x, lp["norm2"]["w"], cfg.norm_eps)
        y2, aux = _ffn_apply(cfg, lp, h2, ctx)
        return x + y2, new_st, aux
    if kind == "rwkv6":
        h = rms_norm(x, lp["norm1"]["w"], cfg.norm_eps)
        tm = lp["tm"]
        if mode == "train":
            y, _ = rwkv6_block(h, tm, cfg, mode="train", chunk=ctx["ssm_chunk"])
            new_st = st
            x = x + y
            h2 = rms_norm(x, lp["norm2"]["w"], cfg.norm_eps)
            cm_shift = jnp.zeros((x.shape[0], cfg.d_model), x.dtype)
            y2, _ = _rwkv6_channel_mix(h2, tm["cm"], cm_shift)
            return x + y2, new_st, aux
        single = mode == "decode"
        h_in = h[:, 0] if single else h
        y, (new_shift, new_wkv) = rwkv6_block(
            h_in, tm, cfg, shift_state=st["shift_tm"], wkv_state=st["wkv"],
            mode="decode" if single else "prefill", chunk=ctx["ssm_chunk"])
        x = x + (y[:, None] if single else y)
        h2 = rms_norm(x, lp["norm2"]["w"], cfg.norm_eps)
        y2, new_cm = _rwkv6_channel_mix(h2, tm["cm"], st["shift_cm"])
        new_st = {"wkv": new_wkv, "shift_tm": new_shift, "shift_cm": new_cm}
        return x + y2, new_st, aux
    if kind == "rglru":
        h = rms_norm(x, lp["norm1"]["w"], cfg.norm_eps)
        if mode == "train":
            y, _ = rglru_block(h, lp["rg"], cfg, mode="train")
            new_st = st
        else:
            single = mode == "decode"
            h_in = h[:, 0] if single else h
            y, (nh, nc) = rglru_block(h_in, lp["rg"], cfg,
                                      state=(st["h"], st["conv"]),
                                      mode="decode" if single else "prefill")
            if single:
                y = y[:, None]
            new_st = {"h": nh, "conv": nc}
        x = x + y
        h2 = rms_norm(x, lp["norm2"]["w"], cfg.norm_eps)
        y2, aux = _ffn_apply(cfg, lp, h2, ctx)
        return x + y2, new_st, aux
    raise ValueError(kind)


# ============================ trunk runners =================================
def _default_ctx(cfg, mode, **kw):
    ctx = {"mode": mode, "q_chunk": 512, "kv_chunk": 512, "ssm_chunk": 32,
           "capacity_factor": 1.25, "batch_axes": None, "tp_axis": None}
    ctx.update(kw)
    return ctx


def _constrain(x, ctx):
    """Pin the residual stream's batch dim to the data axes (GSPMD can
    otherwise drop batch sharding and replicate activations globally)."""
    ax = ctx.get("batch_axes")
    if not ax:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(ax, *([None] * (x.ndim - 1))))


def _constrain_heads(t, ctx):
    """Shard (B, S, H, hd) attention tensors: batch over data axes, heads
    over the model axis.  Keeps the score contraction (over hd) local —
    without this GSPMD shards hd and all-reduces every score block."""
    tp = ctx.get("tp_axis")
    if not tp:
        return t
    from jax.sharding import PartitionSpec as P
    ax = ctx.get("batch_axes")
    return jax.lax.with_sharding_constraint(t, P(ax, None, tp, None))


def _expand_kv(k, G):
    """GQA -> MHA expansion so the head axis is cleanly shardable in the
    XLA path (the Pallas kernels do grouped GQA natively instead)."""
    if G == 1:
        return k
    return jnp.repeat(k, G, axis=2)


def _constrain_cache_seq(t, ctx, seq_axis=1):
    """Sequence-shard a decode cache over the model axis (split-KV /
    flash-decoding): each model rank scores its S/TP slice for all heads and
    GSPMD reduces the tiny partial softmax stats — instead of all-gathering
    the whole cache per layer per step."""
    tp = ctx.get("tp_axis")
    if not tp:
        return t
    from jax.sharding import PartitionSpec as P
    ax = ctx.get("batch_axes")
    spec = [None] * t.ndim
    spec[0] = ax
    spec[seq_axis] = tp
    return jax.lax.with_sharding_constraint(t, P(*spec))


def _run_trunk(cfg, params, x, cache, ctx, *, remat):
    """Head layers -> scanned pattern groups -> tail layers."""
    head, pattern, repeats, tail = layout(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    with_cache = cache is not None

    def one(kind, lp, x, st):
        return apply_block(cfg, kind, lp, x, st, ctx)

    x = _constrain(x, ctx)
    new_head = []
    for i, kind in enumerate(head):
        st = cache["head"][i] if with_cache else None
        x, st2, aux = one(kind, params["head"][i], x, st)
        x = _constrain(x, ctx)
        new_head.append(st2)
        aux_total += aux

    # scanned groups
    def group_body(carry, xs):
        x, auxc = carry
        gp, gst = xs
        new_states = {}
        for pi, kind in enumerate(pattern):
            st = gst[str(pi)] if with_cache else None
            x, st2, aux = apply_block(cfg, kind, gp[str(pi)], x, st, ctx)
            x = _constrain(x, ctx)
            new_states[str(pi)] = st2 if with_cache else 0
            auxc = auxc + aux
        return (x, auxc), (new_states if with_cache else 0)

    if remat == "dots":
        body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.checkpoint_dots)
    elif remat:
        body = jax.checkpoint(group_body)
    else:
        body = group_body
    xs = (params["blocks"], cache["blocks"]) if with_cache \
        else (params["blocks"], {str(pi): jnp.zeros((repeats,))
                                 for pi in range(len(pattern))})
    (x, aux_total), new_blocks = jax.lax.scan(body, (x, aux_total), xs)

    new_tail = []
    for i, kind in enumerate(tail):
        st = cache["tail"][i] if with_cache else None
        x, st2, aux = one(kind, params["tail"][i], x, st)
        x = _constrain(x, ctx)
        new_tail.append(st2)
        aux_total += aux

    new_cache = None
    if with_cache:
        new_cache = dict(cache, head=tuple(new_head), blocks=new_blocks,
                         tail=tuple(new_tail))
    return x, new_cache, aux_total


# ============================ public entry points ===========================
def encode(cfg, params, frontend_emb):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    x = frontend_emb
    if "frontend_proj" in params:
        x = jnp.einsum("bfe,ed->bfd", x, params["frontend_proj"]["w"])

    def body(x, lp):
        h = rms_norm(x, lp["norm1"]["w"], cfg.norm_eps)
        B, F, d = h.shape
        ap = lp["attn"]
        Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = jnp.einsum("bsd,de->bse", h, ap["wq"]).reshape(B, F, Hq, hd)
        k = jnp.einsum("bsd,de->bse", h, ap["wk"]).reshape(B, F, Hkv, hd)
        v = jnp.einsum("bsd,de->bse", h, ap["wv"]).reshape(B, F, Hkv, hd)
        if cfg.qkv_bias:
            q = q + ap["bq"].reshape(Hq, hd)
            k = k + ap["bk"].reshape(Hkv, hd)
            v = v + ap["bv"].reshape(Hkv, hd)
        out = full_attention(q, k, v, causal=False)
        x = x + jnp.einsum("bse,ed->bsd", out.reshape(B, F, Hq * hd), ap["wo"])
        h2 = rms_norm(x, lp["norm2"]["w"], cfg.norm_eps)
        x = x + mlp(h2, lp["ffn"], cfg.mlp_gated)
        return x, 0

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return x


def prepend_frontend(cfg, params, tokens_emb, frontend_emb):
    """VLM: project and prepend patch embeddings to the token embeddings."""
    fe = frontend_emb
    if "frontend_proj" in params:
        fe = jnp.einsum("bfe,ed->bfd", fe, params["frontend_proj"]["w"])
    return jnp.concatenate([fe.astype(tokens_emb.dtype), tokens_emb], axis=1)


def forward(cfg, params, tokens, frontend_emb=None, *, window=None,
            remat=True, q_chunk=512, kv_chunk=512, capacity_factor=1.25,
            batch_axes=None, tp_axis=None):
    """Training forward: full-sequence logits (B, S_total, V) + moe aux loss."""
    x = embed_tokens(tokens, params["embed"]["w"])
    ctx_kw = {}
    if cfg.is_encoder_decoder:
        assert frontend_emb is not None
        ctx_kw["enc_out"] = encode(cfg, params, frontend_emb)
    elif cfg.frontend == "vision" and frontend_emb is not None:
        x = prepend_frontend(cfg, params, x, frontend_emb)
    S = x.shape[1]
    ctx = _default_ctx(cfg, "train", positions=jnp.arange(S), window=window,
                       q_chunk=q_chunk, kv_chunk=kv_chunk,
                       capacity_factor=capacity_factor, batch_axes=batch_axes,
                       tp_axis=tp_axis, **ctx_kw)
    x, _, aux = _run_trunk(cfg, params, x, None, ctx, remat=remat)
    x = rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)
    return lm_logits(x, params), aux


def init_cache(cfg, params, batch, max_len, dtype=jnp.bfloat16, *,
               window=None, frontend_emb=None, kv_dtype=None):
    """Fresh decode state; computes encoder output / cross-KV for enc-dec.

    ``kv_dtype="int8"`` builds a quantized attention ring (int8 payload +
    f32 ``k_scale``/``v_scale`` leaves); ``None``/"bf16" keeps the plain
    ``dtype`` ring — the exactness baseline (DESIGN.md §11)."""
    head, pattern, repeats, tail = layout(cfg)
    cross_len = cfg.frontend_tokens if cfg.is_encoder_decoder else 0
    kv_dtype = None if kv_dtype == "bf16" else kv_dtype
    mk = lambda kind: kvcache.init_layer_state(
        cfg, kind, batch, max_len, dtype, window=window, cross_len=cross_len,
        kv_dtype=kv_dtype)
    cache = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "head": tuple(mk(k) for k in head),
        "blocks": {str(pi): jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (repeats,) + x.shape),
            mk(kind)) for pi, kind in enumerate(pattern)},
        "tail": tuple(mk(k) for k in tail),
    }
    if cfg.is_encoder_decoder:
        assert frontend_emb is not None
        enc_out = encode(cfg, params, frontend_emb)
        cache["enc_out"] = enc_out

        # precompute cross K/V per layer
        def fill_cross(st, lp):
            ap = lp["attn"]
            B, F, _ = enc_out.shape
            Hkv, hd = cfg.num_kv_heads, cfg.head_dim
            xk = jnp.einsum("bfd,de->bfe", enc_out, ap["xk"]).reshape(
                B, F, Hkv, hd).astype(dtype)
            xv = jnp.einsum("bfd,de->bfe", enc_out, ap["xv"]).reshape(
                B, F, Hkv, hd).astype(dtype)
            return dict(st, xk=xk, xv=xv)

        cache["head"] = tuple(fill_cross(st, lp) for st, lp
                              in zip(cache["head"], params["head"]))
        for pi, kind in enumerate(pattern):
            if kind == "attn":
                cache["blocks"][str(pi)] = jax.vmap(fill_cross)(
                    cache["blocks"][str(pi)], params["blocks"][str(pi)])
        cache["tail"] = tuple(fill_cross(st, lp) for st, lp
                              in zip(cache["tail"], params["tail"]))
    return cache


def extend(cfg, params, cache, tokens, *, window=None, frontend_emb=None,
           q_chunk=512, kv_chunk=512, remat=False, capacity_factor=1.25,
           batch_axes=None, tp_axis=None, kernel_backend="xla"):
    """Process a chunk of C tokens against the cache (C == 1 => decode step).

    tokens: (B, C) int32.  Returns (logits_last (B, V), new_cache).
    ``kernel_backend="pallas"`` routes attention through the Pallas kernels
    (``repro.kernels``); "xla" keeps the reference path.
    """
    B, C = tokens.shape
    x = embed_tokens(tokens, params["embed"]["w"])
    if cfg.frontend == "vision" and frontend_emb is not None:
        x = prepend_frontend(cfg, params, x, frontend_emb)
        C = x.shape[1]
    mode = "decode" if C == 1 else "prefill"
    ctx_kw = {}
    if cfg.is_encoder_decoder:
        ctx_kw["enc_out"] = cache.get("enc_out")
    ctx = _default_ctx(cfg, mode, pos0=cache["pos"], window=window,
                       q_chunk=q_chunk, kv_chunk=kv_chunk,
                       capacity_factor=capacity_factor, batch_axes=batch_axes,
                       tp_axis=tp_axis, kernel_backend=kernel_backend,
                       **ctx_kw)
    x, new_cache, _ = _run_trunk(cfg, params, x, cache, ctx, remat=remat)
    new_cache = dict(new_cache, pos=cache["pos"] + C)
    x_last = x[:, -1, :]
    x_last = rms_norm(x_last, params["final_norm"]["w"], cfg.norm_eps)
    return lm_logits(x_last, params), new_cache


def extend_row(cfg, params, pool, tokens, slot, kv_limit=None,
               full_alloc=None, **kw):
    """Chunked prefill directly against batch row ``slot`` of a slot-pool
    cache (DESIGN.md §7): gather the row view, extend it with ``tokens``,
    scatter back only the ``C`` ring positions the chunk wrote (at offset
    ``pos[slot]``) plus the small recurrent state.  Jitted with the pool
    donated, the round trip lowers to in-place row updates — each prompt
    token's KV is written ONCE into the live pool at the row's current
    position, with no scratch cache and no full-row bind scatter at prefill
    completion.

    ``kv_limit`` (static, pow-2) is the caller's bound on the row's live
    prefix and ``full_alloc`` the pool's build-time ``max_len``: positions
    stay below the limit for this chunk, so attention runs on a
    ``kvcache.truncate_rings`` view and scores O(kv_limit) keys instead of
    O(alloc) — early prompt chunks do a fraction of a full-ring extend's
    attention work (something the position-oblivious scratch path cannot).

    tokens: (1, C) int32; ``slot`` may be a traced int32.
    Returns (logits_last (1, V), new_pool).
    """
    one = kvcache.read_row(pool, slot)
    start = one["pos"][0]
    view = one if kv_limit is None else \
        kvcache.truncate_rings(one, kv_limit, full_alloc)
    logits, view = extend(cfg, params, view, tokens, **kw)
    return logits, kvcache.write_row_slice(pool, view, slot, start,
                                           tokens.shape[1])


def _decode_block(cfg, kind, lp, x, st, layer, active, ctx):
    """One block of a pooled decode step, against one layer's state as the
    layer loop carries it (``layer``: traced index into a scanned group's
    stacked leaves, None for an unrolled layer).

    Ring payload stays whole: ``_attn_mix_extend`` writes this step's K/V
    into it in place and reads its window (``kvcache.write_decode_step``,
    through ``ctx["pool_write"]``).  Per-row state whose bytes do not grow
    with the ring (recurrent ``wkv``/``h``, ``conv``/``shift_*``, cross-
    attention ``xk``/``xv``) is read for rows ``[0, rows)``, advanced by the
    same :func:`apply_block` prefill runs, and written back under the
    active mask (``kvcache.select_rows``); a leaf the block hands back
    unchanged is not written at all."""
    rows = x.shape[0]

    def take(a):
        if layer is not None:
            a = jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
        return a[:rows]

    def put(a, v):
        if layer is None:
            return jax.lax.dynamic_update_slice(a, v, (0,) * a.ndim)
        return jax.lax.dynamic_update_slice(a, v[None],
                                            (layer,) + (0,) * (a.ndim - 1))

    ring = {n: a for n, a in st.items() if n in kvcache._RING_PAYLOAD}
    rest = {n: take(a) for n, a in st.items() if n not in ring}
    write = functools.partial(
        kvcache.write_decode_step, layer=layer, active=active,
        kv_limit=ctx["kv_limit"], full=ctx["full_alloc"])
    x, new, _ = apply_block(cfg, kind, lp, x, {**ring, **rest},
                            dict(ctx, pool_write=write))
    out = dict(st)
    for n, v in new.items():
        if n in ring:
            out[n] = v
        elif v is not rest[n]:
            out[n] = put(st[n], kvcache.select_rows(active, v, rest[n]))
    return x, out


def _decode_trunk(cfg, params, x, pool, active, ctx):
    """The layers of one pooled decode step with the whole pool as the loop
    carry: head layers, a ``lax.scan`` over each scanned group's stacked
    params and layer index (the pool in the carry, not in ``xs``/``ys``,
    so its buffers are updated in place, never stacked anew), tail layers.
    Rows ``[rows, P)`` of the pool are neither read nor written."""
    head, pattern, repeats, tail = layout(cfg)

    def layers(kinds, lps, states, x):
        out = []
        for kind, lp, st in zip(kinds, lps, states):
            x, st = _decode_block(cfg, kind, lp, x, st, None, active, ctx)
            x = _constrain(x, ctx)
            out.append(st)
        return x, tuple(out)

    def body(carry, xs):
        x, blocks = carry
        gp, layer = xs
        blocks = dict(blocks)
        for pi, kind in enumerate(pattern):
            x, blocks[str(pi)] = _decode_block(cfg, kind, gp[str(pi)], x,
                                               blocks[str(pi)], layer,
                                               active, ctx)
            x = _constrain(x, ctx)
        return (x, blocks), None

    x = _constrain(x, ctx)
    x, new_head = layers(head, params["head"], pool["head"], x)
    (x, blocks), _ = jax.lax.scan(
        body, (x, pool["blocks"]),
        (params["blocks"], jnp.arange(repeats, dtype=jnp.int32)))
    x, new_tail = layers(tail, params["tail"], pool["tail"], x)
    return x, dict(pool, head=new_head, blocks=blocks, tail=new_tail)


def decode_step(cfg, params, cache, tokens, active, kv_limit=None,
                full_alloc=None, **kw):
    """One masked decode iteration over a slot-pool cache (DESIGN.md §3).

    tokens: (rows,) int32 last token of pool rows ``[0, rows)``; active:
    (rows,) bool slot mask.  ``cache`` is the whole pool (``P >= rows``
    rows; jit it donated): rows ``[rows, P)`` are not computed, and rows
    with ``active == False`` are computed but leave no trace — their K/V,
    positions and recurrent state stay bit-identical.  Returns
    (next_tokens (rows,), logits (rows, V), new_cache) with greedy next
    tokens computed on-device.

    The pool is the carry of the step's layer loop: each layer writes its
    one new K/V position a row in place (``kvcache.write_decode_step``) and
    attention reads the layer's ``[:rows, :kv_limit]`` window of it.
    ``kv_limit`` (static, pow-2) bounds the live prefix of every ACTIVE row
    (mirroring :func:`extend_row`) and ``full_alloc`` is the cache's
    build-time ``max_len``.  The caller guarantees ``pos < kv_limit`` for
    every active row after the step — a row that wrapped its ring (``pos >=
    full_alloc``) needs ``kv_limit >= full_alloc``, which reads whole rings.
    Windowed leaves (``alloc < full_alloc``) are always read whole.
    """
    rows = tokens.shape[0]
    pos = cache["pos"][:rows]
    if "enc_out" in cache:
        kw["enc_out"] = cache["enc_out"][:rows]
    ctx = _default_ctx(cfg, "decode", pos0=pos, kv_limit=kv_limit,
                       full_alloc=full_alloc, **kw)
    x = embed_tokens(tokens[:, None], params["embed"]["w"])
    x, cache = _decode_trunk(cfg, params, x, cache, active, ctx)
    cache = dict(cache, pos=cache["pos"].at[:rows].set(
        jnp.where(active, pos + 1, pos)))
    x_last = rms_norm(x[:, -1, :], params["final_norm"]["w"], cfg.norm_eps)
    logits = lm_logits(x_last, params)
    return logits.argmax(-1).astype(jnp.int32), logits, cache


def decode_run(cfg, params, cache, tokens, active, n_steps: int,
               kv_limit=None, full_alloc=None, **kw):
    """``n_steps`` fused masked decode iterations under ONE ``lax.scan``
    (DESIGN.md §6).

    Between scheduler-visible events the decode batch is fixed, so there is
    no reason to return to Python per token: the scan keeps the KV pool, the
    per-slot last tokens and the greedy feedback loop on device and emits the
    whole ``(n_steps, rows)`` token block at the boundary.  Each step is a
    :func:`decode_step` with the pool carried through the scan, so a fused
    run is token-exact against ``n_steps`` separate calls.

    Positions advance ``n_steps`` times inside the scan, so the caller's
    ``kv_limit`` must cover the run's END: ``max live pos + n_steps <=
    kv_limit`` across the active rows.

    tokens: (rows,) int32 last token of pool rows ``[0, rows)``; active:
    (rows,) bool.  Returns (token_block (n_steps, rows), final_tokens
    (rows,), new_cache).
    """
    def body(carry, _):
        pool, toks = carry
        nxt, _, pool = decode_step(cfg, params, pool, toks, active,
                                   kv_limit, full_alloc, **kw)
        return (pool, jnp.where(active, nxt, toks)), nxt

    (pool, toks), block = jax.lax.scan(body, (cache, tokens), None,
                                       length=int(n_steps))
    return block, toks, pool


def prefill(cfg, params, tokens, *, max_len=None, window=None,
            frontend_emb=None, dtype=jnp.bfloat16, q_chunk=512, kv_chunk=512,
            capacity_factor=1.25, batch_axes=None, tp_axis=None):
    """Full prefill: build a fresh cache and run the whole prompt through it."""
    B, S = tokens.shape
    extra = cfg.frontend_tokens if cfg.frontend == "vision" else 0
    max_len = max_len or (S + extra)
    fe = frontend_emb if cfg.is_encoder_decoder else None
    cache = init_cache(cfg, params, B, max_len, dtype, window=window,
                       frontend_emb=fe)
    vfe = frontend_emb if cfg.frontend == "vision" else None
    return extend(cfg, params, cache, tokens, window=window, frontend_emb=vfe,
                  q_chunk=q_chunk, kv_chunk=kv_chunk,
                  capacity_factor=capacity_factor, batch_axes=batch_axes,
                  tp_axis=tp_axis)
