#!/usr/bin/env python3
"""Chip smoke test: Llama-3.2-3B at full width in bf16, served on a TPU.

    python chip_smoke.py           # one chip: XLA + bf16 KV, then Pallas + int8 KV
    python chip_smoke.py --dual    # two or more chips: prefill staged on
                                   # device 1 vs co-located on device 0

Drives the main path through the entry points a user calls:
``ServingFrontend`` -> ``RealAgentXPUEngine`` -> scheduler ->
``JaxRealBackend`` -> slot-pool KV cache, with the engine built by
``launch/serve.py``'s own construction (``--real --full-width``).  Weights
are random, drawn from ``--seed``; so are the flows: a shared 256-token
system prompt (the prefix cache hits), tails of 64-192 tokens, 16-32 output
tokens, six proactive flows up front and two reactive flows submitted while
the first streams.

It passes only if every flow completes with its full token count, nothing
faulted, retried, was rejected or timed out, the slot accounting audits
clean, and every flow's first token agrees with a float32 reference forward
(``models/reference.py``) run on the same chip.  It refuses to run on
anything but a TPU.  The last line of stdout is one JSON object
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Wall times it
prints are host-clock times of a smoke run, compiles included: not device
metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# Engine flags of the served configuration (launch/serve.py's parser): the
# registry Llama-3.2-3B in bf16, a 16-slot pool of 2048-token rings that may
# not grow (growth would hold the old and the doubled pool at once), and the
# slot audit after every event-loop turn.
ENGINE_FLAGS = ["--real", "--full-width", "--arch", "llama3.2-3b",
                "--pool-slots", "16", "--pool-slots-max", "16",
                "--strict-invariants"]
SINGLE = ["--no-dual-device"]
PHASES = {"xla+bf16": SINGLE,
          "pallas+int8": SINGLE + ["--kernel-backend", "pallas",
                                   "--kv-dtype", "int8"]}

SYSTEM_PROMPT = 256
N_PROACTIVE, N_REACTIVE = 6, 2
REF_LEN = 512  # >= longest prompt (256 + 192) + longest output (32)

# First-token tolerance, in logits of the float32 reference.  The served
# path computes in bf16 (8-bit mantissa, 2^-9 relative rounding per op).
# Measured on CPU at these widths with these random weights (logit std 1.1,
# max ~5): the rms |bf16 - f32| logit error is 0.014 at 2 layers and 0.025
# at 8, growing as sqrt(depth), so ~0.047 at 28 layers and ~0.066 for the
# difference of two logits.  0.5 is ~7.5 of those sigmas, while a token
# drawn from the bulk of the vocabulary sits 4-5 logits below the max.  An
# int8 KV pool adds its quantization error: the served int8 path measured
# 0.030 rms at 4 layers (CPU), so ~0.08 at 28 and ~0.11 for a difference,
# which leaves 0.5 at ~4.5 sigmas for the Pallas + int8 phase.
FIRST_TOKEN_TOL = 0.5


def log(msg: str) -> None:
    print(msg, flush=True)


def make_flows(vocab: int, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, SYSTEM_PROMPT)
    flows = []
    for i in range(N_PROACTIVE + N_REACTIVE):
        tail = rng.integers(0, vocab, int(rng.integers(64, 193)))
        flows.append({"id": i, "reactive": i >= N_PROACTIVE,
                      "tokens": np.concatenate([system, tail])[None],
                      "max_new": int(rng.integers(16, 33))})
    return flows


def _devices_of(tree) -> set:
    import jax
    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        out |= set(leaf.devices())
    return out


def serve_phase(name, flags, model, flows, compiles, *, inspect=None):
    """Serve ``flows`` through a fresh front-end + engine; returns the
    phase record (outputs, counters, problems).  Collects the previous
    phase's engine first (flow handles and the front-end reference each
    other, so only the cycle collector frees it), so phases never hold two
    pools at once."""
    from repro.core.requests import Priority
    from repro.launch import serve
    from repro.launch.frontend import ServingFrontend
    args = serve.build_parser().parse_args(flags)
    cfg, params, dtype = model
    gc.collect()
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    eng = serve.real_engine(args, cfg, params, dtype)
    fe = ServingFrontend(eng).start()
    handles, problems, mid_stream = {}, [], None
    try:
        for f in flows:
            if not f["reactive"]:
                handles[f["id"]] = fe.submit(
                    f["tokens"], priority=Priority.PROACTIVE,
                    max_new_tokens=f["max_new"], flow_id=f["id"])
        # reactive flows arrive once the first proactive flow streams
        first = handles[flows[0]["id"]]
        for _ in range(2):
            first.next_token(timeout=600)
        mid_stream = fe.stats()["flows_in_flight"]
        for f in flows:
            if f["reactive"]:
                handles[f["id"]] = fe.submit(
                    f["tokens"], priority=Priority.REACTIVE,
                    max_new_tokens=f["max_new"], flow_id=f["id"])
        fe.close(timeout=900)
    except Exception as e:  # recorded and failed below, never hidden
        problems.append(f"serving raised {e!r}")
    wall = time.perf_counter() - t0
    if fe._worker_error is not None:
        problems.append(f"front-end worker error {fe._worker_error!r}")
    outputs = {}
    for f in flows:
        h = handles.get(f["id"])
        if h is None or h.status != "completed":
            problems.append(f"flow {f['id']}: status "
                            f"{None if h is None else h.status} "
                            f"({None if h is None else h.fault})")
            continue
        toks = list(h.tokens_out)
        if len(toks) != f["max_new"]:
            problems.append(f"flow {f['id']}: {len(toks)} tokens, "
                            f"expected {f['max_new']}")
        outputs[f["id"]] = toks
    st, fst = eng.stats(), fe.stats()
    for key in ("flow_faults", "quarantined_flows", "device_fault_retries"):
        if st[key]:
            problems.append(f"{key} = {st[key]}")
    for key in ("admission_rejections", "deadline_aborts", "cancelled_flows",
                "backpressure_disconnects"):
        if fst[key]:
            problems.append(f"{key} = {fst[key]}")
    audit = eng.backend.validate()
    if audit:
        problems.append(f"validate(): {audit}")
    if st["free_slots"] != st["pool_slots"]:
        problems.append(f"{st['free_slots']} slots free at exit of "
                        f"{st['pool_slots']}")
    extra = inspect(eng) if inspect is not None else {}
    problems += extra.pop("problems", [])
    c1 = compiles.snapshot()
    rec = {"name": name, "outputs": outputs, "problems": problems,
           "stats": st, "frontend": fst, "wall_s": wall,
           "in_flight_at_reactive_submit": mid_stream,
           "compiles": {k: c1[k] - c0[k] for k in c1}, **extra}
    return rec


def report_phase(rec) -> None:
    st, c = rec["stats"], rec["compiles"]
    served = sum(len(t) for t in rec["outputs"].values())
    log(f"[{rec['name']}] {len(rec['outputs'])} flows completed, {served} "
        f"tokens served; {c['compiles']} compiles ({c['fresh_compiles']} "
        f"fresh, {c['cache_hits']} from the persistent cache, "
        f"{c['compile_seconds']:.1f} s compiling: set-up); "
        f"smoke wall time {rec['wall_s']:.1f} s (host clock, compiles "
        f"included; not a device metric)")
    log(f"[{rec['name']}] engine: {st['jit_compilations']} jitted programs, "
        f"{st['decode_device_calls']} decode calls, "
        f"{st['prefill_device_calls']} prefill calls, "
        f"{st['prefix_hits']} prefix hits ({st['prefix_hit_tokens']} "
        f"prompt tokens copied), last decode dispatch "
        f"{st['decode_rows']} rows x kv_limit {st['decode_kv_limit']}, "
        f"kv {st['kv_dtype']}, kernels {st['kernel_backend']}, "
        f"{rec['in_flight_at_reactive_submit']} flows in flight when the "
        f"reactive flows arrived")
    for p in rec["problems"]:
        log(f"[{rec['name']}] FAIL: {p}")


def pallas_decode_lowering(eng) -> dict:
    """The decode program the Pallas phase ran must carry the Mosaic
    kernel (``tpu_custom_call``), not an XLA fallback."""
    be = eng.backend
    keys = [k for k in be._jit_cache if k[0] == "decode_run"]
    if not keys:
        return {"problems": ["no fused decode program was compiled"]}
    _, pool, n, rows, kvl = keys[0]
    text = be._decode_run_fn(pool, n, rows, kvl).lower(
        be.params, be._pool, be._toks, be._mask).as_text()
    ok = "tpu_custom_call" in text
    log(f"[pallas+int8] decode program {keys[0][1:]} lowers with "
        f"tpu_custom_call: {ok}")
    return {} if ok else {"problems": ["decode program has no "
                                       "tpu_custom_call"]}


def reference_check(model, flows, recs) -> list:
    """First tokens against the float32 reference (matmul precision
    "highest"), and every served token against a bf16 B=1 greedy
    reference fed the served prefix (teacher-forced agreement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.reference import reference_logits
    cfg, params, _ = model
    gc.collect()  # the last phase's engine and pool

    def scorer(dtype):
        @jax.jit
        def score(params, toks, nxt):
            z = reference_logits(cfg, params, toks, dtype)[0]
            gap = z.max(-1) - jnp.take_along_axis(z, nxt[0][:, None], -1)[:, 0]
            return z.argmax(-1), gap
        return score

    f32, bf16 = scorer(jnp.float32), scorer(jnp.bfloat16)
    problems = []
    for rec in recs:
        agree = total = exact = within = 0
        worst = worst_any = 0.0
        for f in flows:
            out = rec["outputs"].get(f["id"])
            if not out:
                continue
            plen = f["tokens"].shape[1]
            seq = np.zeros((1, REF_LEN), np.int32)
            seq[0, :plen] = f["tokens"][0]
            seq[0, plen:plen + len(out) - 1] = out[:-1]
            nxt = np.zeros((1, REF_LEN), np.int32)
            nxt[0, plen - 1:plen - 1 + len(out)] = out
            with jax.default_matmul_precision("highest"):
                am32, gap32 = (np.asarray(a) for a in
                               f32(params, jnp.asarray(seq), jnp.asarray(nxt)))
            am16, gap16 = (np.asarray(a) for a in
                           bf16(params, jnp.asarray(seq), jnp.asarray(nxt)))
            g = float(gap32[plen - 1])
            worst = max(worst, g)
            exact += int(am32[plen - 1] == out[0])
            rec.setdefault("first_gap", {})[f["id"]] = g
            if g > FIRST_TOKEN_TOL:
                problems.append(
                    f"[{rec['name']}] flow {f['id']}: first token {out[0]} "
                    f"sits {g:.3f} below the float32 reference max "
                    f"(token {int(am32[plen - 1])}); tolerance "
                    f"{FIRST_TOKEN_TOL}")
            span = slice(plen - 1, plen - 1 + len(out))
            agree += int((am16[span] == np.asarray(out)).sum())
            total += len(out)
            within += int((gap32[span] <= FIRST_TOKEN_TOL).sum())
            worst_any = max(worst_any, float(gap32[span].max()))
        log(f"[{rec['name']}] first tokens: {exact}/{len(rec['outputs'])} "
            f"are the float32 reference argmax, worst gap {worst:.4f} "
            f"(tolerance {FIRST_TOKEN_TOL}); served tokens agreeing with "
            f"the bf16 B=1 greedy reference: {agree}/{total} "
            f"({agree / max(total, 1):.4f}); within the tolerance of the "
            f"float32 reference: {within}/{total}, worst gap "
            f"{worst_any:.4f}")
    return problems


def run_single(model, flows, compiles) -> list:
    recs = []
    for name, extra in PHASES.items():
        inspect = pallas_decode_lowering if "pallas" in name else None
        rec = serve_phase(name, ENGINE_FLAGS + extra, model, flows, compiles,
                          inspect=inspect)
        report_phase(rec)
        recs.append(rec)
    problems = [p for r in recs for p in r["problems"]]
    return problems + reference_check(model, flows, recs)


def dual_placement(eng) -> dict:
    """Where the dual backend put its state, read from the arrays."""
    import jax
    be = eng.backend
    dec, pf = jax.devices()[0], jax.devices()[1]
    st = be.stats()
    problems = []
    if not st["dual_device"] or st["staged_prefills"] <= 0:
        problems.append(f"dual_device {st['dual_device']}, "
                        f"{st['staged_prefills']} staged prefills")
    if st["handoff_device_calls"] != st["staged_prefills"]:
        problems.append(f"{st['handoff_device_calls']} handoffs for "
                        f"{st['staged_prefills']} staged prefills")
    placed = {"params_pf": _devices_of(be._params_pf),
              "staging": _devices_of(be._staging_free),
              "pool": _devices_of(be._pool)}
    want = {"params_pf": {pf}, "staging": {pf}, "pool": {dec}}
    for key, devs in placed.items():
        if devs != want[key]:
            problems.append(f"{key} on {sorted(map(str, devs))}, expected "
                            f"{sorted(map(str, want[key]))}")
    log(f"[dual] {st['staged_prefills']} staged prefills, "
        f"{st['handoff_device_calls']} handoffs "
        f"({st['kv_bytes_handoff']} KV bytes), co-located: "
        f"{st['colocated_hits']} prefix-hit / {st['colocated_backpressure']} "
        f"backpressure / {st['colocated_affinity']} affinity; params copy on {sorted(map(str, placed['params_pf']))}"
        f", staging caches on {sorted(map(str, placed['staging']))}, pool on "
        f"{sorted(map(str, placed['pool']))}")
    return {"problems": problems}


def run_dual(model, flows, compiles) -> list:
    staged = serve_phase("dual", ENGINE_FLAGS + ["--prefill-device", "1"],
                         model, flows, compiles, inspect=dual_placement)
    report_phase(staged)
    colo = serve_phase("co-located", ENGINE_FLAGS + SINGLE, model, flows,
                       compiles)
    report_phase(colo)
    problems = staged["problems"] + colo["problems"]
    problems += reference_check(model, flows, [staged, colo])
    for f in flows:
        a = staged["outputs"].get(f["id"], [None])[0]
        b = colo["outputs"].get(f["id"], [None])[0]
        if a != b and not all(r.get("first_gap", {}).get(f["id"], 1e9)
                              <= FIRST_TOKEN_TOL for r in (staged, colo)):
            problems.append(f"flow {f['id']}: first token {a} staged vs "
                            f"{b} co-located")
    same = sum(staged["outputs"].get(f["id"], [None])[0]
               == colo["outputs"].get(f["id"], [0])[0] for f in flows)
    log(f"[dual] first tokens identical to the co-located run: "
        f"{same}/{len(flows)}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dual", action="store_true",
                    help="run only the dual-device path (prefill staged on "
                         "device 1, pool on device 0) and its co-located "
                         "comparison; needs two or more chips")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch import serve
        from repro.launch.compile_cache import (CompileStats,
                                                configure_compile_cache)
        cache_dir = configure_compile_cache()
        compiles = CompileStats()
        devs = jax.devices()
    except Exception as e:
        print(json.dumps({"ok": False, "error": f"set-up failed: {e!r}"}))
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"no TPU: JAX found {device}; this smoke test never serves on "
              f"another platform", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}))
        return 2
    if args.dual and len(devs) < 2:
        print(json.dumps({"ok": False, "device": device,
                          "error": "--dual needs two or more chips"}))
        return 2
    log(f"[setup] device {device}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    model = serve.build_model(serve.build_parser().parse_args(
        ENGINE_FLAGS + ["--seed", str(args.seed)]))
    cfg, params, _ = model
    jax.block_until_ready(params)
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    log(f"[setup] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} q / {cfg.num_kv_heads} kv heads x "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"{cfg.num_params() / 1e9:.3f} B params, {n_bytes / 2**30:.2f} GiB "
        f"bf16 on {sorted(map(str, _devices_of(params)))} "
        f"(init {time.perf_counter() - t0:.1f} s host clock)")
    flows = make_flows(cfg.vocab_size, args.seed)
    log(f"[setup] {len(flows)} flows: prompts "
        f"{[f['tokens'].shape[1] for f in flows]}, outputs "
        f"{[f['max_new'] for f in flows]} tokens")
    try:
        problems = (run_dual if args.dual else run_single)(
            model, flows, compiles)
    except Exception as e:
        import traceback
        traceback.print_exc()
        problems = [f"raised {e!r}"]
    for i, d in enumerate(devs[:2] if args.dual else devs[:1]):
        mem = d.memory_stats() or {}
        log(f"[memory] device {i} peak_bytes_in_use "
            f"{mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{mem.get('bytes_limit', 0) / 2**30:.2f} GiB")
    total = compiles.snapshot()
    log(f"[compile] {total['compiles']} compiles, {total['fresh_compiles']} "
        f"fresh, {total['cache_hits']} persistent-cache hits, "
        f"{total['compile_seconds']:.1f} s (set-up)")
    for p in problems:
        log(f"FAIL: {p}")
    ok = not problems
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
