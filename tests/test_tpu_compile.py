"""Compile the pool attention kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
does not apply the chip's tiling rules.  These tests hand the Mosaic
compiler Llama-3.2-3B attention widths (Hq=24, Hkv=8, hd=128, a 2048-slot
ring) for a ``v5e:2x2`` topology that is described, not attached, so a
BlockSpec the chip would refuse fails here without a chip.  Nothing runs;
a pass says the program compiles, not what it computes or how fast.

The topology is described inside a fixture (never at import) because only
one process at a time may load the TPU library: each xdist worker collects
the same tests, and only the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_pool

HQ, HKV, HD, RING = 24, 8, 128, 2048  # Llama-3.2-3B at max_len 2048
CHUNK = 128  # prefill chunk (the HEG chunk size of this config)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kv_specs(B, S, kv, sharding, layout):
    """k, v (payload) and their scales (None for bf16) in ``layout``:
    "bshd" is the pool ring, "bhsd" the head-major view of the prefill
    kernel."""
    dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    shape = (B, S, HKV, HD) if layout == "bshd" else (B, HKV, S, HD)
    sshape = (B, S, HKV) if layout == "bshd" else (B, HKV, S)
    k = _spec(shape, dtype, sharding)
    sc = _spec(sshape, jnp.float32, sharding) if kv == "int8" else None
    return k, k, sc, sc


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("ring,kv_limit", [
    (RING, 64), (RING, 128), (RING, RING),
    (64, None),  # a 64-slot truncate_rings view, as elastic decode hands it
])
def test_decode_attention_compiles_for_v5e(one_chip, kv, B, ring, kv_limit):
    k, v, ks, vs = _kv_specs(B, ring, kv, one_chip, "bshd")
    q = _spec((B, HQ, HD), jnp.bfloat16, one_chip)
    slot_pos = _spec((B, ring), jnp.int32, one_chip)
    cur = _spec((B,), jnp.int32, one_chip)

    def fn(q, k, v, slot_pos, cur, ks, vs):
        return decode_attention(q, k, v, slot_pos, cur, k_scale=ks,
                                v_scale=vs, kv_limit=kv_limit,
                                interpret=False)
    _compile(fn, q, k, v, slot_pos, cur, ks, vs)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("ring,chunk,kv_limit", [
    (RING, CHUNK, 64), (RING, CHUNK, 128), (RING, CHUNK, RING),
    (512, 64, None),  # a 64-token chunk against a truncated 512-slot view
])
def test_flash_attention_pool_compiles_for_v5e(one_chip, kv, B, ring, chunk,
                                               kv_limit):
    k, v, ks, vs = _kv_specs(B, ring, kv, one_chip, "bhsd")
    q = _spec((B, HQ, chunk, HD), jnp.bfloat16, one_chip)
    pos_q = _spec((B, chunk), jnp.int32, one_chip)
    pos_kv = _spec((B, ring), jnp.int32, one_chip)

    def fn(q, k, v, pos_q, pos_kv, ks, vs):
        return flash_attention_pool(q, k, v, pos_q, pos_kv, k_scale=ks,
                                    v_scale=vs, kv_limit=kv_limit,
                                    interpret=False)
    _compile(fn, q, k, v, pos_q, pos_kv, ks, vs)


# -- the pooled decode programs, as the backend builds them ------------------
# Published widths with the depth cut: Qwen2.5-3B (36 layers -> 4) on the
# XLA path, one 8-layer stage of Qwen2.5-32B (-> 2) on the Pallas path; both
# with the benchmark's ring of 4096 slots and its slot counts.
DECODE_MODELS = {
    "qwen2.5-3b": (dict(num_layers=4, d_model=2048, num_heads=16,
                        num_kv_heads=2, d_ff=11008, vocab_size=151936,
                        tie_embeddings=True), 16, "xla"),
    "qwen2.5-32b-L8": (dict(num_layers=2, d_model=5120, num_heads=40,
                            num_kv_heads=8, d_ff=27648, vocab_size=152064,
                            tie_embeddings=False), 12, "pallas"),
}
DECODE_RING = 4096


def _hlo_outputs(text):
    """(opcode, dtype, shape, root opcode of a fusion's computation) of
    every instruction outside fused computations: the buffers the program
    materialises."""
    import re
    comps, roots, cur = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            name = head.group(1)
            continue
        if cur is not None and line.startswith("  "):
            cur.append(line)
            root = re.match(r"\s+ROOT %\S+ = \S+ ([\w\-]+)\(", line)
            if root:
                roots[name] = root.group(1)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    out = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} "
                         r"([\w\-]+)\(", line)
            if not m:
                continue
            called = re.search(r"calls=%([\w.\-]+)", line)
            out.append((m.group(3), m.group(1),
                        tuple(int(d) for d in m.group(2).split(",") if d),
                        roots.get(called.group(1)) if called else None))
    return out


@pytest.mark.parametrize("n_steps", [None, 8], ids=["decode", "decode_run"])
@pytest.mark.parametrize("rows,kv_limit", [(None, 2048), (None, 4096),
                                           (8, 1024)])
@pytest.mark.parametrize("model", sorted(DECODE_MODELS))
def test_pooled_decode_writes_kv_in_place_for_v5e(one_chip, monkeypatch,
                                                  model, rows, kv_limit,
                                                  n_steps):
    """The decode programs keep the pool as their loop carry: the only ops
    that output a stacked ring leaf ``[layers, rows|pool, kv_limit|ring,
    ...]`` are the one in-place scatter per leaf, and the temporaries stay
    far below the pool's ring bytes (the parent's slab views took more than
    the ring itself)."""
    from repro.configs.base import ModelConfig
    from repro.core.backend import decode_program
    from repro.kernels import ops as kops
    from repro.models import init_cache, init_params
    # compile the Mosaic kernels, as on the chip (CPU interprets them)
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)
    widths, pool_size, kb = DECODE_MODELS[model]
    rows = rows or pool_size
    cfg = ModelConfig(name=model, arch_type="dense", head_dim=HD,
                      rope_theta=1e6, qkv_bias=True, norm_eps=1e-6, **widths)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: _spec(s.shape, s.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, None, pool_size, DECODE_RING, jnp.bfloat16)))
    toks = _spec((pool_size,), jnp.int32, one_chip)
    mask = _spec((pool_size,), jnp.bool_, one_chip)
    fn = decode_program(cfg, kb, DECODE_RING, pool_size, rows, kv_limit,
                        n_steps)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool, toks, mask).compile()

    ring_leaves = pool["blocks"]["0"]
    ring_bytes = sum(x.size * x.dtype.itemsize for x in
                     jax.tree_util.tree_leaves(ring_leaves))
    L, hkv = widths["num_layers"], widths["num_kv_heads"]
    slabs = {(L, r, e) + tail for r in (rows, pool_size)
             for e in (kv_limit, DECODE_RING) for tail in ((), (hkv, HD))}
    stacked = [(op, root) for op, dtype, shape, root
               in _hlo_outputs(compiled.as_text()) if shape in slabs
               and op not in ("parameter", "get-tuple-element", "tuple",
                              "while", "bitcast")]
    scatters = [op for op in stacked if op == ("fusion", "scatter")]
    # memory-space transfers the compiler may schedule to stage the small
    # int32 slot_pos leaf in VMEM around its scatter
    staged = [op for op in stacked if op[0] in ("copy-done", "custom-call")]
    assert len(scatters) == len(ring_leaves), stacked
    assert len(stacked) == len(scatters) + len(staged), stacked
    assert len(staged) <= 2 and (not staged or ring_leaves[
        "slot_pos"].size * 4 < ring_bytes / 100), stacked

    # what the kernel wrapper and the weights' layout add, neither of which
    # this program changes: the Pallas kernel reads one layer's K and V
    # window through a head-major copy, and a fused run may hoist layout
    # copies of the q/k/v projections out of its step loop
    allowance = 0
    if kb == "pallas":
        allowance += 2 * rows * kv_limit * hkv * HD * 2
    if n_steps:
        allowance += sum(params["blocks"]["0"]["attn"][w].size * 2
                         for w in ("wq", "wk", "wv"))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < ring_bytes / 10 + allowance, (temp, ring_bytes, allowance)
