"""A configuration file (``bench/configs/<name>.json``) as the program runs
it: the published config mapped onto the program's ``ModelConfig``, and
the weights drawn on the device from the seed, both by the configuration's
family (``bench/family.py``).

The weights are the benchmark's, not the program's: the family draws
every leaf in the tree layout the program reads, and the reference in
``bench/reference.py`` reads the same tree, so nothing it compares with
was made by the code under test.
"""
from __future__ import annotations

import json
import os

from bench import family

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str, configs_dir: str = None) -> dict:
    path = os.path.join(configs_dir or os.path.join(BENCH_DIR, "configs"),
                        f"{name}.json")
    with open(path) as f:
        return json.load(f)


def model_config(conf: dict, data_dir: str = None):
    """The program's ``ModelConfig`` for a published config, by the family
    of its ``model_type`` (``data_dir/families/`` first)."""
    fam = family.load(conf["config"].get("model_type"), data_dir)
    return family.bind(fam.model_config(conf), fam)


def init_weights(cfg, seed_key, dtype):
    """All weights from one key in one jitted program, on the default
    device."""
    return family.of(cfg).init_weights(cfg, seed_key, dtype)
