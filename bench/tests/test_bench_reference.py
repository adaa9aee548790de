"""The benchmark's reference is the program's plain forward, unchanged in
its arithmetic by the query and vocabulary blocking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import family
from bench import reference as bref
from bench.model import init_weights
from repro.configs import get_tiny_config
from repro.models.reference import reference_logits as program_reference


@pytest.fixture(scope="module")
def tiny():
    # qkv bias, untied head; the program's config, served by the dense
    # family the benchmark binds to it
    cfg = family.bind(get_tiny_config("qwen2.5-32b"), family.load("qwen2"))
    params = init_weights(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              cfg.vocab_size)
    return cfg, params, toks


def test_equals_the_program_reference(tiny):
    cfg, params, toks = tiny
    with jax.default_matmul_precision("highest"):
        want = program_reference(cfg, params, toks)
        for qb in (512, 16, 7):
            got = bref.reference_logits(cfg, params, toks, q_block=qb)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_equals_it_with_tied_embeddings(tiny):
    cfg, _, toks = tiny
    cfg = cfg.with_overrides(tie_embeddings=True)
    params = init_weights(cfg, jax.random.PRNGKey(2), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            bref.reference_logits(cfg, params, toks, q_block=16),
            program_reference(cfg, params, toks), rtol=2e-5, atol=2e-5)


def test_head_summary_matches_full_logits(tiny):
    cfg, params, toks = tiny
    with jax.default_matmul_precision("highest"):
        z = bref.reference_logits(cfg, params, toks[:1])[0]
        h = bref.reference_hidden(cfg, params, toks[:1])[0]
        tgt = jnp.arange(toks.shape[1]) % cfg.vocab_size
        best, arg, t = bref.head_summary(params, h, tgt, v_block=100)
    np.testing.assert_allclose(best, z.max(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(arg, z.argmax(-1))
    np.testing.assert_allclose(t, z[jnp.arange(z.shape[0]), tgt],
                               rtol=1e-5, atol=1e-5)


def test_float8_operands_move_the_logits(tiny):
    cfg, params, toks = tiny
    with jax.default_matmul_precision("highest"):
        z = bref.reference_logits(cfg, params, toks)
        zc = bref.reference_logits(cfg, params, toks,
                                   operand_dtype=jnp.float8_e4m3fn)
    err = float(jnp.abs(zc - z).max())
    assert 1e-3 < err < 1.0
