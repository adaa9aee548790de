"""The family module that serves a configuration.

A configuration's published ``model_type`` names its family: the module
``families/<model_type>.py``, looked for under the cell's data directory
first and then under ``bench/families/``, and loaded by path as the
metric readers are.  A family module exports:

* ``model_config(conf)``: the published config mapped onto the program's
  ``ModelConfig``, with the file's ``reduced`` and ``assumed`` honoured;
* ``init_weights(cfg, key, dtype)``: the weights drawn from the key in
  one jitted program, in the tree layout the program reads for the
  family;
* ``reference_hidden(cfg, params, tokens, compute_dtype, *, q_block,
  operand_dtype)``: the plain float32 forward up to the final norm,
  rounding every product's operands through ``bench.reference.rounder``;
  the output head stays ``bench/reference.py``'s;
* ``matmul_params(cfg)``: the weights one token multiplies (for routed
  experts, those it activates); ``attn_flops(cfg, ctx)``, linear in the
  ``ctx`` keys; ``kv_bytes_per_token(cfg, kv_dtype)``.

``bench/model.py``, ``bench/reference.py`` and ``bench/flops.py`` find a
family from the ``ModelConfig`` alone (``of``), since the metric readers
get nothing else: ``model.model_config`` binds the configuration's name
to its family.  The dense families are ``qwen2`` and ``llama``.
"""
from __future__ import annotations

import importlib.util
import os

FAMILIES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "families")
_loaded = {}  # file path -> module
_bound = {}  # configuration name -> module


def load(model_type: str, data_dir: str = None):
    """The family module of ``model_type``; an error names the file to
    add when there is none."""
    dirs = [os.path.join(data_dir, "families")] if data_dir else []
    for d in dirs + [FAMILIES_DIR]:
        path = os.path.join(d, f"{model_type}.py")
        if not os.path.isfile(path):
            continue
        if path not in _loaded:
            spec = importlib.util.spec_from_file_location(
                f"bench_family_{model_type}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _loaded[path] = mod
        return _loaded[path]
    raise NotImplementedError(
        f"no benchmark family for model_type {model_type!r}: add "
        f"bench/families/{model_type}.py")


def bind(cfg, module):
    """Serve ``cfg`` (and every configuration of its name) by ``module``."""
    _bound[cfg.name] = module
    return cfg


def of(cfg):
    """The family bound to ``cfg``'s name."""
    try:
        return _bound[cfg.name]
    except KeyError:
        raise LookupError(f"configuration {cfg.name!r} has no family: "
                          f"build it with bench.model.model_config, or "
                          f"bench.family.bind it") from None
