"""The plain reference forward (models/reference.py) against the system's
own paths: the training forward, and prefill followed by decode through a
KV cache, in float32 at the tiny Llama preset."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import extend, forward, init_params, prefill
from repro.models.reference import reference_logits, supports_reference


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = get_tiny_config("llama3.2-3b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_reference_matches_training_forward(tiny_llama):
    cfg, params, tokens = tiny_llama
    with jax.default_matmul_precision("highest"):
        ref = reference_logits(cfg, params, tokens)
        got, _ = forward(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_reference_matches_prefill_then_decode(tiny_llama):
    """Prefill 16 tokens into a ring cache, then decode the next 8 one at a
    time: every step's logits equal the reference at that position."""
    cfg, params, tokens = tiny_llama
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference_logits(cfg, params, tokens))
        lg, cache = prefill(cfg, params, tokens[:, :16], max_len=32,
                            dtype=jnp.float32)
        steps = [lg]
        for t in range(16, 24):
            lg, cache = extend(cfg, params, cache, tokens[:, t:t + 1])
            steps.append(lg)
    got = np.stack([np.asarray(s) for s in steps[:-1]], axis=1)
    np.testing.assert_allclose(got, ref[:, 15:23], rtol=1e-4, atol=1e-4)


def test_reference_refuses_unsupported_configs():
    assert supports_reference(get_tiny_config("llama3.2-3b"))
    cfg = get_tiny_config("qwen2-moe-a2.7b")
    assert not supports_reference(cfg)
    with pytest.raises(NotImplementedError):
        reference_logits(cfg, {}, jnp.zeros((1, 4), jnp.int32))
