"""Model families as files of their own (``bench/family.py``).

A family the tests bring in their data directory (``data/families/
tinylm.py``: no QKV bias, untied head) comes with a config and a cell
file and nothing else, and a run of it is served and checked end to end;
taken away, the run stops with an error naming the file.  The dense
family in ``bench/families/qwen2.py`` is the code the benchmark ran
before families were files: weights and counts are pinned to what the
former functions gave at the tiny size.
"""
import hashlib
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, family, flops, model, reference, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = json.load(open(os.path.join(DATA, "bench.json")))
CELL = {"name": "tinylm.tiny_mix", "config": "tinylm", "traffic": "tiny_mix",
        "chips": 1, "why": "a family the tests bring"}
PEAKS = json.load(open(os.path.join(run.BENCH_DIR, "peaks.json")))[
    "TPU v5 lite"]


def tinylm_run(data_dir=DATA, seed=2 ** 31 + 7):
    args = run.parse(["--workload", CELL["name"], "--seed", str(seed),
                      "--seconds", "8", "--trace", "0"])
    bench = dict(BENCH, workloads=BENCH["workloads"] + [CELL])
    return run.run(args, bench=bench, data_dir=data_dir, require_tpu=False,
                   log=lambda m: None)


def test_new_family_is_served_and_checked(monkeypatch):
    monkeypatch.setattr(check, "MIN_TOKENS", 150)
    monkeypatch.setattr(check, "MIN_FLOWS", 12)
    out = tinylm_run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] < out["attempted"]
    cfg = model.model_config(model.load_config(
        "tinylm", os.path.join(DATA, "configs")), DATA)
    assert not cfg.qkv_bias and not cfg.tie_embeddings
    assert family.of(cfg) is family.load("tinylm", DATA)


def test_family_file_taken_away_names_it(tmp_path):
    data = tmp_path / "data"
    shutil.copytree(DATA, data, ignore=shutil.ignore_patterns("families"))
    with pytest.raises(NotImplementedError, match="families/tinylm.py"):
        tinylm_run(str(data))


def test_model_mfu_counts_by_the_family(monkeypatch):
    """The reader finds the family from the record's config alone and
    counts with its own ``matmul_params``."""
    cfg = model.model_config(model.load_config(
        "tinylm", os.path.join(DATA, "configs")), DATA)
    fam, calls = family.of(cfg), []
    own = fam.matmul_params
    monkeypatch.setattr(fam, "matmul_params",
                        lambda c: calls.append(c.name) or own(c))
    read = run.metric_reader("model_mfu",
                             os.path.join(run.BENCH_DIR, "metrics"))
    # d 512, 8/2 heads x 64, ffn 1024, 2 layers, vocab 8192, untied
    per_layer = 2 * 512 * 512 + 2 * 512 * 128 + 3 * 512 * 1024 + 2 * 512
    want = 2 * per_layer + 512 + 8192 * 512
    assert flops.matmul_params(cfg) == want
    flow = {"prompt": [1] * 10, "walls": [0.5, 0.6]}
    rec = {"cfg": cfg, "peaks": PEAKS, "ws": 0.0, "we": 1.0,
           "flows": [flow]}
    total = flops.prompt_flops(cfg, 10) + flops.token_flops(cfg, 11)
    assert total == 11 * 2 * want + 4 * (55 + 11) * 8 * 64 * 2
    assert read(rec) == pytest.approx(
        100.0 * total / PEAKS["bf16_flops_per_s"], rel=1e-12)
    assert calls and set(calls) == {"tinylm"}


def _digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(jnp.asarray(x).astype(jnp.float32)).tobytes())
    return h.hexdigest()[:16]


def test_dense_family_is_the_former_code():
    """Digests and counts the benchmark's functions gave at the tiny size
    before they moved into ``bench/families/qwen2.py``; the reference
    through the dispatch is the family's, bit for bit."""
    cfg = model.model_config(model.load_config(
        "tiny", os.path.join(DATA, "configs")))
    assert family.of(cfg) is family.load("qwen2")
    assert _digest(model.init_weights(cfg, jax.random.PRNGKey(7),
                                      jnp.float32)) == "cfa9e55407588d15"
    params = model.init_weights(cfg, jax.random.PRNGKey(7), jnp.bfloat16)
    assert _digest(params) == "4f84c4eeec8bdb9a"
    assert (flops.matmul_params(cfg), flops.kv_bytes_per_token(cfg),
            flops.kv_bytes_per_token(cfg, "int8"),
            flops.attn_flops(cfg, 1000), flops.prompt_flops(cfg, 1037),
            flops.token_flops(cfg, 77), flops.decode_attn_bytes(cfg, 2048)
            ) == (8_654_848, 1024, 512, 4_096_000, 20_154_634_240,
                  17_625_088, 2_097_152)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 96), 0,
                              cfg.vocab_size)
    fam = family.load("qwen2")
    with jax.default_matmul_precision("highest"):
        for op in (None, jnp.float8_e4m3fn):
            np.testing.assert_array_equal(
                reference.reference_hidden(cfg, params, toks, q_block=32,
                                           operand_dtype=op),
                fam.reference_hidden(cfg, params, toks, jnp.float32,
                                     q_block=32, operand_dtype=op))


def test_llama_is_the_dense_family_without_bias():
    conf = model.load_config("tiny", os.path.join(DATA, "configs"))
    conf = dict(conf, name="tiny-llama",
                config=dict(conf["config"], model_type="llama"))
    cfg = model.model_config(conf)
    dense = family.load("qwen2")
    assert not cfg.qkv_bias
    assert family.of(cfg).init_weights is dense.init_weights
    assert flops.matmul_params(cfg) == 8_654_848 - 2 * (512 + 2 * 128)


def test_unbound_config_names_the_way_to_bind_it():
    from repro.configs import get_tiny_config
    cfg = get_tiny_config("llama3.2-3b").with_overrides(name="never-bound")
    with pytest.raises(LookupError, match="bench.model.model_config"):
        flops.matmul_params(cfg)
