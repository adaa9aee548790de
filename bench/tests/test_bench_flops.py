"""Operation and byte counts against values worked by hand."""
import json
import os

from bench import flops, model

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg(name):
    return model.model_config(model.load_config(name))


def test_qwen25_3b_counts():
    cfg = _cfg("qwen2.5-3b")
    # per layer: q 2048*2048 + k,v 2*2048*256 + o 2048*2048 + biases
    # 2048+256+256 + ffn 3*2048*11008 + norms 2*2048 = 77,076,992;
    # x36 + final norm 2048 + tied head 151936*2048 = 3,085,938,688
    assert flops.matmul_params(cfg) == 3_085_938_688
    assert flops.matmul_params(cfg) == cfg.num_params()  # tied: all of it
    # K and V, 36 layers x 2 kv heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(cfg) == 36_864
    # QK and PV of one query over 1000 keys: 4*1000*16*128*36
    assert flops.attn_flops(cfg, 1000) == 294_912_000
    assert flops.token_flops(cfg, 1) == 2 * 3_085_938_688 + 294_912


def test_qwen25_32b_stage_counts():
    cfg = _cfg("qwen2.5-32b-L8")
    # per layer: q 5120*5120 + k,v 2*5120*1024 + o 5120*5120 + biases
    # 5120+2*1024 + ffn 3*5120*27648 + norms 2*5120 = 487,605,248;
    # x8 + final norm 5120 + head 5120*152064 = 4,679,414,784
    assert flops.matmul_params(cfg) == 4_679_414_784
    assert flops.kv_bytes_per_token(cfg) == 32_768  # 8 x 2 x 8 x 128 x 2
    assert flops.decode_attn_bytes(cfg, 2048) == 2048 * 32_768


def test_prompt_flops_is_the_sum_over_its_tokens():
    cfg = _cfg("qwen2.5-3b")
    n = 37
    assert flops.prompt_flops(cfg, n) == sum(flops.token_flops(cfg, i + 1)
                                             for i in range(n))


def test_roofline_names_its_bound():
    peaks = json.load(open(os.path.join(HERE, "..", "peaks.json")))[
        "TPU v5 lite"]
    t, bound = flops.roofline_s(0, 819e9, peaks)
    assert bound == "bandwidth" and abs(t - 1.0) < 1e-12
    t, bound = flops.roofline_s(197e12, 1, peaks)
    assert bound == "compute" and abs(t - 1.0) < 1e-12
