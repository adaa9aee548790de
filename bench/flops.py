"""Operations and bytes the served work requires, from a configuration's
widths and the generated traffic alone: never from the program's counters,
so the count is the same whatever implements it, and work the program
skips (a cache hit) or repeats cannot raise it.

A token at context length ``c`` (the keys it attends to, itself included)
requires ``2 * matmul_params + attn_flops(c)`` FLOPs; both terms and the
KV bytes of a token are the configuration's family's
(``bench/family.py``).
"""
from __future__ import annotations

from bench import family

KV_BYTES = {"bf16": 2, "int8": 1}


def matmul_params(cfg) -> int:
    """Weights one token multiplies: every layer's projections and FFN (for
    routed experts, those it activates; biases and norm gains included)
    and the output head; the embedding lookup is a gather, not a
    product."""
    return family.of(cfg).matmul_params(cfg)


def attn_flops(cfg, ctx: int) -> int:
    """The attention's products of one query over ``ctx`` keys, all
    layers."""
    return family.of(cfg).attn_flops(cfg, ctx)


def token_flops(cfg, ctx: int) -> int:
    return 2 * matmul_params(cfg) + attn_flops(cfg, ctx)


def prompt_flops(cfg, n: int) -> int:
    """A prompt of ``n`` tokens none of which was sent before: token ``i``
    attends to ``i + 1`` keys (``attn_flops`` is linear in the keys)."""
    return n * 2 * matmul_params(cfg) + attn_flops(cfg, n * (n + 1) // 2)


def kv_bytes_per_token(cfg, kv_dtype: str = "bf16") -> int:
    """What the KV cache holds for one token in every layer."""
    return family.of(cfg).kv_bytes_per_token(cfg, kv_dtype)


def decode_attn_bytes(cfg, ctx: int, kv_dtype: str = "bf16") -> int:
    """KV a decode step must read for one token at context ``ctx``."""
    return ctx * kv_bytes_per_token(cfg, kv_dtype)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time on a chip with ``peaks``, and which bound holds."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bandwidth") if t_bytes >= t_flops \
        else (t_flops, "compute")
