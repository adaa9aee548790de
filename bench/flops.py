"""Operations and bytes the served work requires, from a configuration's
widths and the generated traffic alone: never from the program's counters,
so the count is the same whatever implements it, and work the program
skips (a cache hit) or repeats cannot raise it.

A token at context length ``c`` (the keys it attends to, itself included)
requires ``2 * matmul_params + 4 * c * heads * head_dim * layers`` FLOPs:
every non-embedding weight once (the output head included, which a tied
model reads from its embedding), plus QK^T and PV.
"""
from __future__ import annotations

KV_BYTES = {"bf16": 2, "int8": 1}


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


def token_flops(cfg, ctx: int) -> int:
    return 2 * matmul_params(cfg) + attn_flops(cfg, ctx)


def prompt_flops(cfg, n: int) -> int:
    """A prompt of ``n`` tokens none of which was sent before: token ``i``
    attends to ``i + 1`` keys."""
    tri = n * (n + 1) // 2
    return n * 2 * matmul_params(cfg) + 4 * tri * cfg.num_heads \
        * cfg.head_dim * cfg.num_layers


def kv_bytes_per_token(cfg, kv_dtype: str = "bf16") -> int:
    """K and V of one token in every layer."""
    return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim \
        * KV_BYTES[kv_dtype]


def decode_attn_bytes(cfg, ctx: int, kv_dtype: str = "bf16") -> int:
    """KV a decode step must read for one token at context ``ctx``."""
    return ctx * kv_bytes_per_token(cfg, kv_dtype)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time on a chip with ``peaks``, and which bound holds."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bandwidth") if t_bytes >= t_flops \
        else (t_flops, "compute")
