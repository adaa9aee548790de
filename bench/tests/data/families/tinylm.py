"""A family the CPU tests bring in their own data directory, as a new
family comes in as one file: ``model_type`` "tinylm", the dense
equations of ``bench/families/qwen2.py`` with no QKV bias and an untied
head, mapped and counted here."""
from bench import family

_dense = family.load("qwen2")
init_weights = _dense.init_weights
reference_hidden = _dense.reference_hidden
attn_flops = _dense.attn_flops
kv_bytes_per_token = _dense.kv_bytes_per_token


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    c = conf["config"]
    return ModelConfig(
        name=conf["name"], arch_type="dense", source=conf["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), qkv_bias=False,
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=False)


def matmul_params(cfg) -> int:
    """q, k, v and o, the gated FFN and both norms of every layer, the
    final norm and the untied head."""
    d, hd = cfg.d_model, cfg.head_dim
    attn = 2 * d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
    per_layer = attn + 3 * d * cfg.d_ff + 2 * d
    return cfg.num_layers * per_layer + d + cfg.vocab_size * d
