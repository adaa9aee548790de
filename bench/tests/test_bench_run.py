"""A whole run of the harness at a tiny size on the CPU: the look for a
chip is skipped, everything else runs as on the chip.

The tiny cell's limit (``data/cells/tiny.tiny_mix.json``) was set as the
chip cells' were: the program's widest gap reads 0.010 and 0.011 on two
seeds, the float8 control's 0.071 and 0.072.  A run of the sound program
must come out correct; the control, and each fault the cell can have
planted in the timed path, must not.
"""
import json
import os

import jax.numpy as jnp
import pytest

from bench import check, run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = json.load(open(os.path.join(DATA, "bench.json")))


@pytest.fixture(autouse=True)
def small_sample(monkeypatch):
    monkeypatch.setattr(check, "MIN_TOKENS", 150)
    monkeypatch.setattr(check, "MIN_FLOWS", 12)


def tiny_run(seed=11, trace=0, control=0):
    args = run.parse(["--workload", "tiny.tiny_mix", "--seed", str(seed),
                      "--seconds", "8", "--trace", str(trace),
                      "--control", str(control)])
    return run.run(args, bench=BENCH, data_dir=DATA, require_tpu=False,
                   log=lambda m: None)


@pytest.mark.parametrize("control", [0, 1])
def test_sound_run_is_correct_and_control_is_not(control):
    """The same seed twice: the program's run is correct; with the float8
    control in the program's place, the harness's verdict is false."""
    out = tiny_run(seed=2 ** 31 + 11, control=control)
    checks = out["checks"]
    # a loaded CPU may refuse a flow at the tiny pool's cap: counted, and
    # no part of what is compared
    assert out["attempted"] > 0 and out["failed"] < out["attempted"]
    assert set(out["metrics"]) == {"reactive_tpot_p50_ms",
                                   "proactive_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    if control:
        assert not out["correct"], checks
        gap = checks["control_widest_logit_gap"]
        assert gap["value"] > gap["limit"]
        assert "widest_logit_gap" not in checks
    else:
        assert out["correct"], checks
        assert "control_widest_logit_gap" not in checks


def test_engine_is_built_as_its_file_states(monkeypatch):
    """Every setting the configuration states reaches the launcher as a
    flag, and an engine that differs from the file is refused."""
    from repro.launch import serve
    from bench import model
    conf = model.load_config("qwen2.5-32b-L8")
    flags = run.engine_flags(conf)
    for flag, value in (("--kv-dtype", "bf16"), ("--kernel-backend", "pallas"),
                        ("--pool-slots", "12"), ("--pool-slots-max", "12"),
                        ("--decode-segment-steps", "8")):
        assert flags[flags.index(flag) + 1] == value
    assert "--no-prefix-cache" in flags
    tiny = model.load_config("tiny", os.path.join(DATA, "configs"))
    cfg = model.model_config(tiny)
    import jax
    params = model.init_weights(cfg, jax.random.PRNGKey(0), jnp.float32)
    real_engine = serve.real_engine

    def int8_default(args, *a, **kw):
        args.kv_dtype = "int8"  # as a changed launcher default would
        return real_engine(args, *a, **kw)
    monkeypatch.setattr(serve, "real_engine", int8_default)
    with pytest.raises(RuntimeError, match="kv_dtype"):
        run.build_engine(tiny, cfg, params, 1)


def _patch_decode(monkeypatch, fault):
    """Plant ``fault`` in both decode programs the backend builds."""
    import repro.models as models
    run_orig, step_orig = models.decode_run, models.decode_step

    def decode_run(cfg, params, cache, tokens, active, n_steps, **kw):
        if fault == "half_batch":
            active = active & (jnp.arange(active.shape[0])
                               < active.shape[0] // 2)
        block, toks, new = run_orig(cfg, params, cache, tokens, active,
                                    n_steps, **kw)
        return block, toks, cache if fault == "state_unchanged" else new

    def decode_step(cfg, params, cache, tokens, active, **kw):
        if fault == "half_batch":
            active = active & (jnp.arange(active.shape[0])
                               < active.shape[0] // 2)
        nxt, logits, new = step_orig(cfg, params, cache, tokens, active,
                                     **kw)
        return nxt, logits, cache if fault == "state_unchanged" else new

    monkeypatch.setattr(models, "decode_run", decode_run)
    monkeypatch.setattr(models, "decode_step", decode_step)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    if fault == "token_altered":
        from repro.core.backend import JaxRealBackend
        emit = JaxRealBackend._emit

        def altered(self, req, token):
            return emit(self, req, (int(token) + 1) % self.cfg.vocab_size)
        monkeypatch.setattr(JaxRealBackend, "_emit", altered)
    else:
        _patch_decode(monkeypatch, fault)
    out = tiny_run(seed=23)
    assert not out["correct"], out["checks"]


def test_traced_run_reports_per_layer_metrics():
    """Counters and host-clock metrics read on any platform; the CPU has
    no device plane, peaks or memory limit, so the device metrics stay
    silent rather than read 0."""
    out = tiny_run(seed=5, trace=1)
    assert out["correct"], out["checks"]
    assert {"generator_lag_p95_ms", "tokens_per_decode_call",
            "host_syncs_per_token", "compiles_in_window"} <= \
        set(out["metrics"])
    assert not {"model_mfu", "decode_attn_roofline",
                "hbm_peak_share"} & set(out["metrics"])
    assert "busy_s" in out["device"] and "window_s" in out["device"]
