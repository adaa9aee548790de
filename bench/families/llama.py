"""Llama: ``qwen2.py``'s dense equations, whose ``model_config`` gives
the QKV bias to ``model_type`` "qwen2" alone."""
from bench import family

_dense = family.load("qwen2")
model_config = _dense.model_config
init_weights = _dense.init_weights
reference_hidden = _dense.reference_hidden
matmul_params = _dense.matmul_params
attn_flops = _dense.attn_flops
kv_bytes_per_token = _dense.kv_bytes_per_token
