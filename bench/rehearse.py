#!/usr/bin/env python3
"""Compile rehearsal for a described TPU v5e: how many pool slots fit.

    JAX_PLATFORMS=cpu python bench/rehearse.py qwen2.5-3b [slots ...]

Nothing runs and nothing is allocated: the weights and the KV pool are
shapes (``jax.eval_shape``), handed to the TPU compiler for one chip of a
``v5e:2x2`` topology that is described, not attached.  For each slot count
it compiles the two largest programs the served path runs, written here as
the backend writes them from ``repro.models``' public functions:

* a fused decode segment of 8 steps over every pool row at the full ring
  (``decode_run`` with the pool and token vector donated);
* an in-pool prefill chunk of the HEG chunk size at the full ring
  (``extend_row`` into the donated pool).

and prints ``memory_analysis()``: arguments, outputs, aliased (donated)
bytes and temporaries.  Arguments plus temporaries, less what is aliased,
is what the program needs on the chip while it runs.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

GiB = 2 ** 30


def rehearse(name: str, slot_counts):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model
    from repro.core.annotation import INTEL_CORE_ULTRA_5_125H
    from repro.core.heg import HEG
    from repro.models import decode_run, extend_row, init_cache

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    conf = model.load_config(name)
    cfg = model.model_config(conf)
    max_len = int(conf["max_len"])
    kb = conf["kernel_backend"]
    chunk = HEG(cfg, INTEL_CORE_ULTRA_5_125H).chunk_size

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(
        lambda k: model.init_weights(cfg, k, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    p_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    print(f"{name}: params {p_bytes / GiB:.2f} GiB, kernels {kb}, "
          f"HEG chunk {chunk}, ring {max_len}")
    for slots in slot_counts:
        pool = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, None, slots, max_len, jnp.bfloat16)))
        pool_bytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree_util.tree_leaves(pool))
        toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
        mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=chip)

        def dec(params, pool, toks, mask):
            block, t, pool = decode_run(cfg, params, pool, toks, mask, 8,
                                        kv_limit=max_len, full_alloc=max_len,
                                        kernel_backend=kb)
            return block, t, pool

        buf = jax.ShapeDtypeStruct((1, max_len), jnp.int32, sharding=chip)
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

        def pre(params, pool, tok_buf, start, slot):
            chunk_toks = jax.lax.dynamic_slice(tok_buf, (jnp.int32(0), start),
                                               (1, chunk))
            logits, pool = extend_row(cfg, params, pool, chunk_toks, slot,
                                      kv_limit=max_len, full_alloc=max_len,
                                      kernel_backend=kb)
            return logits.argmax(-1), pool

        for label, fn, args, donate in (
                ("decode_run x8", dec, (params, pool, toks, mask), (1, 2)),
                ("prefill chunk", pre, (params, pool, buf, i32, i32), (1,))):
            m = jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
                .memory_analysis()
            need = (m.argument_size_in_bytes + m.output_size_in_bytes
                    - m.alias_size_in_bytes + m.temp_size_in_bytes)
            print(f"  {slots:3d} slots: pool {pool_bytes / GiB:.2f} GiB; "
                  f"{label}: args {m.argument_size_in_bytes / GiB:.2f}, out "
                  f"{m.output_size_in_bytes / GiB:.2f}, aliased "
                  f"{m.alias_size_in_bytes / GiB:.2f}, temp "
                  f"{m.temp_size_in_bytes / GiB:.2f} GiB -> needs "
                  f"{need / GiB:.2f} GiB", flush=True)


if __name__ == "__main__":
    rehearse(sys.argv[1], [int(s) for s in sys.argv[2:]] or [8, 16, 24, 32])
