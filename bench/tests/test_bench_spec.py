"""BENCHMARK.json and the files the harness finds by name."""
import json
import os
import re

from bench import model, run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_metric_has_its_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py")), m["name"]
        assert callable(run.metric_reader(m["name"],
                                          os.path.join(BENCH, "metrics")))


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        for sub, name in (("traffic", w["traffic"]), ("cells", w["name"])):
            assert os.path.exists(os.path.join(BENCH, sub, f"{name}.json"))
        cell = json.load(open(os.path.join(BENCH, "cells",
                                           f"{w['name']}.json")))
        assert cell["reactive_rate_per_s"] > 0
        assert cell["widest_gap_limit"] > 0


def test_names_and_keys_follow_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_each_pair_of_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_staged_mix_is_the_agent_mix():
    """The dual-stage cell is paired with the one-chip 3B cell: its mix has a
    name of its own but offers the same work."""
    def mix(name):
        d = json.load(open(os.path.join(BENCH, "traffic", f"{name}.json")))
        d.pop("why")
        return d
    assert mix("agent_mix_staged") == mix("agent_mix")


def test_config_file_declares_reductions():
    for c in SPEC["configs"]:
        conf = model.load_config(c["name"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in conf["reduced"]:
            assert key in conf["published"]


def test_published_widths():
    """The widths of the configurations as published (Qwen2.5-3B-Instruct
    and Qwen2.5-32B-Instruct config.json)."""
    want = {
        "qwen2.5-3b": dict(num_layers=36, d_model=2048, num_heads=16,
                           num_kv_heads=2, head_dim=128, d_ff=11008,
                           vocab_size=151936, tie_embeddings=True,
                           qkv_bias=True, rope_theta=1e6, norm_eps=1e-6),
        "qwen2.5-32b-L8": dict(num_layers=8, d_model=5120, num_heads=40,
                               num_kv_heads=8, head_dim=128, d_ff=27648,
                               vocab_size=152064, tie_embeddings=False,
                               qkv_bias=True, rope_theta=1e6),
    }
    for name, fields in want.items():
        cfg = model.model_config(model.load_config(name))
        for k, v in fields.items():
            assert getattr(cfg, k) == v, (name, k)
    assert model.load_config("qwen2.5-32b-L8")["published"] == \
        {"num_hidden_layers": 64}


def test_a_new_metric_is_found_by_name(tmp_path):
    (tmp_path / "new_metric.py").write_text(
        "def read(rec):\n    return rec['x'] * 2\n")
    assert run.metric_reader("new_metric", str(tmp_path))({"x": 21}) == 42


def test_a_new_config_and_mix_are_found_by_name(tmp_path):
    conf = model.load_config("qwen2.5-3b")
    conf["name"] = "other"
    (tmp_path / "other.json").write_text(json.dumps(conf))
    assert model.load_config("other", str(tmp_path))["name"] == "other"
    spec = {"workloads": [{"name": "other.mix", "config": "other",
                           "traffic": "mix", "chips": 1}]}
    assert run.load_cell("other.mix", spec)["traffic"] == "mix"
