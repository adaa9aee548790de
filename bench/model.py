"""A configuration file (``bench/configs/<name>.json``) as the program runs
it: the published config mapped onto the program's ``ModelConfig``, and
the weights drawn on the device from the seed.

The weights are the benchmark's, not the program's: one jitted program
draws every leaf in the served dtype, in the tree layout the program's
dense decoder reads (``embed``, ``blocks/0`` stacked over layers,
``final_norm``, ``lm_head`` unless tied).  The reference in
``bench/reference.py`` reads the same tree, so nothing it compares with
was made by the code under test.
"""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str, configs_dir: str = None) -> dict:
    path = os.path.join(configs_dir or os.path.join(BENCH_DIR, "configs"),
                        f"{name}.json")
    with open(path) as f:
        return json.load(f)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a dense decoder's published
    config (Qwen2 / Llama style keys)."""
    from repro.configs.base import ModelConfig
    c = conf["config"]
    if c.get("model_type") not in ("qwen2", "llama"):
        raise NotImplementedError(
            f"no mapping for model_type {c.get('model_type')!r}")
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
    import jax
    import jax.numpy as jnp
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
