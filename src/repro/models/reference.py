"""Plain reference forward for dense GQA decoders (Llama family).

Written from the architecture's definition with ``jax.numpy`` only: no KV
cache, no ring, no slot pool, no Pallas, none of ``transformer.py``'s
helpers.  It is what served tokens are checked against: a full causal pass
over the whole sequence in ``compute_dtype`` (float32 for the reference,
with ``jax.default_matmul_precision("highest")`` set by the caller).
Weights may be stored in another dtype; each layer's are cast inside the
layer scan, so float32 copies of the whole model never exist at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def supports_reference(cfg) -> bool:
    """Dense decoders with full (unwindowed) GQA attention."""
    return (cfg.arch_type == "dense" and not cfg.use_mla and not cfg.is_moe
            and not cfg.layer_pattern and not cfg.first_k_dense_layers
            and cfg.sliding_window is None and cfg.frontend == "none"
            and not cfg.is_encoder_decoder)


def reference_logits(cfg, params, tokens, compute_dtype=jnp.float32):
    """Logits (B, S, vocab) of every position of ``tokens`` (B, S)."""
    if not supports_reference(cfg):
        raise NotImplementedError(
            f"the plain reference covers dense full-attention decoders; "
            f"{cfg.name} is not one")
    B, S = tokens.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.norm_eps

    def c(w):
        return w.astype(compute_dtype)

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

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        ap, fp = lp["attn"], lp["ffn"]
        h = norm(x, lp["norm1"]["w"])
        q, k, v = h @ c(ap["wq"]), h @ c(ap["wk"]), h @ c(ap["wv"])
        if cfg.qkv_bias:
            q, k, v = q + c(ap["bq"]), k + c(ap["bk"]), v + c(ap["bv"])
        q = rope(q.reshape(B, S, Hq, hd))
        k = jnp.repeat(rope(k.reshape(B, S, Hkv, hd)), Hq // Hkv, axis=2)
        v = jnp.repeat(v.reshape(B, S, Hkv, hd), Hq // Hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(causal, s / jnp.sqrt(jnp.float32(hd)), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, Hq * hd)
        x = x + o @ c(ap["wo"])
        h = norm(x, lp["norm2"]["w"])
        if cfg.mlp_gated:
            f = jax.nn.silu(h @ c(fp["wg"])) * (h @ c(fp["w1"]))
        else:
            f = jax.nn.gelu(h @ c(fp["w1"]))
        return x + f @ c(fp["w2"]), None

    embed = params["embed"]["w"]
    x = c(jnp.take(embed, tokens, axis=0))
    x, _ = jax.lax.scan(layer, x, params["blocks"]["0"])
    x = norm(x, params["final_norm"]["w"])
    if "lm_head" in params:
        return (x @ c(params["lm_head"]["w"])).astype(jnp.float32)
    return jnp.einsum("bsd,vd->bsv", x, c(embed)).astype(jnp.float32)
