"""Qwen2: dense GQA decoders with a bias on the q, k and v projections
(``model_type`` "qwen2"; ``llama.py`` serves the same equations without
the bias).

The weights are the benchmark's, not the program's: one jitted program
draws every leaf in the served dtype, in the tree layout the program's
dense decoder reads (``embed``, ``blocks/0`` stacked over layers,
``final_norm``, ``lm_head`` unless tied).  The reference forward is a
copy of the program's ``models/reference.py`` (the same equations, with
``jax.numpy`` only: no KV cache, ring, slot pool or Pallas), so no later
change to the program can move what served tokens are compared with;
its queries are taken in blocks, each row's softmax seeing every key as
before.

A token at context length ``c`` (the keys it attends to, itself
included) requires ``2 * matmul_params + 4 * c * heads * head_dim *
layers`` FLOPs: every non-embedding weight once (the output head
included, which a tied model reads from its embedding), plus QK^T and PV.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.flops import KV_BYTES
from bench.reference import rounder


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a dense decoder's published
    config (Qwen2 / Llama style keys)."""
    from repro.configs.base import ModelConfig
    c = conf["config"]
    heads = c["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=heads, num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        qkv_bias=c["model_type"] == "qwen2",
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        mlp_gated=c.get("hidden_act", "silu") == "silu")


def init_weights(cfg, seed_key, dtype):
    """All weights from one key in one jitted program, on the default
    device.  Projections have the usual 0.02 scale (output projections
    shrunk by depth); biases and norm gains are drawn too, so a program
    that dropped either would serve other tokens."""
    d, L = cfg.d_model, cfg.num_layers
    hq, hkv, hd, f = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.d_ff)
    out_std = 0.02 / (2 * L) ** 0.5

    def nrm(k, shape, std):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)

    def gain(k):
        return (1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)
                ).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 12)
        attn = {"wq": nrm(ks[0], (d, hq * hd), 0.02),
                "wk": nrm(ks[1], (d, hkv * hd), 0.02),
                "wv": nrm(ks[2], (d, hkv * hd), 0.02),
                "wo": nrm(ks[3], (hq * hd, d), out_std)}
        if cfg.qkv_bias:
            attn["bq"] = nrm(ks[4], (hq * hd,), 0.2)
            attn["bk"] = nrm(ks[5], (hkv * hd,), 0.2)
            attn["bv"] = nrm(ks[6], (hkv * hd,), 0.2)
        ffn = {"w1": nrm(ks[7], (d, f), 0.02), "w2": nrm(ks[8], (f, d),
                                                        out_std)}
        if cfg.mlp_gated:
            ffn["wg"] = nrm(ks[9], (d, f), 0.02)
        return {"norm1": {"w": gain(ks[10])}, "norm2": {"w": gain(ks[11])},
                "attn": attn, "ffn": ffn}

    def build(key):
        k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
        params = {"embed": {"w": nrm(k_embed, (cfg.vocab_size, d), 0.02)},
                  "final_norm": {"w": gain(k_norm)},
                  "head": (), "tail": (),
                  # one layer's draws live at a time
                  "blocks": {"0": jax.lax.map(
                      layer, jax.random.split(k_layers, L))}}
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": nrm(k_head, (d, cfg.vocab_size), 0.02)}
        return params

    return jax.jit(build)(seed_key)


def reference_hidden(cfg, params, tokens, compute_dtype=jnp.float32, *,
                     q_block=512, operand_dtype=None):
    """Final-norm hidden states (B, S, d) of every position of ``tokens``."""
    B, S = tokens.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.norm_eps
    rnd = rounder(operand_dtype, compute_dtype)

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


def matmul_params(cfg) -> int:
    """Weights multiplied per token: every layer's projections and FFN
    (biases and norm gains included) and the output head; the embedding
    lookup is a gather, not a product."""
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    if cfg.qkv_bias:
        attn += (hq + 2 * hkv) * hd
    ffn = (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
    per_layer = attn + ffn + 2 * d
    return cfg.num_layers * per_layer + d + cfg.vocab_size * d


def attn_flops(cfg, ctx: int) -> int:
    """QK^T and PV of one query over ``ctx`` keys, all layers."""
    return 4 * ctx * cfg.num_heads * cfg.head_dim * cfg.num_layers


def kv_bytes_per_token(cfg, kv_dtype: str = "bf16") -> int:
    """K and V of one token in every layer."""
    return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim \
        * KV_BYTES[kv_dtype]
