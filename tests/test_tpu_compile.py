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
