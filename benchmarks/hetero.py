"""Stage-decoupled dual-device benchmark (BENCH_hetero.json, DESIGN.md §14).

Three gated properties, measured on the SAME prefill-heavy trace in the
same process (within-run ratios, so they transfer across runner hardware):

  * ``overlap_throughput_ratio`` — aggregate decode tokens/s of the
    dual-device engine (staged prefill on device 1 overlapping decode on
    device 0) over the serialized single-device engine.
  * ``token_exact`` — every flow of a mixed reactive/proactive trace
    (mid-run preemption, shared-prefix hits landing on the decode pool)
    streams byte-identical tokens in both modes.
  * ``reactive_ttft_ratio`` — wall p50 TTFT of reactives injected under
    concurrent proactive prefill load, dual over single (cost ratio:
    dual-device dispatch must not slow the reactive path down).

Honesty note: two FORCED host-platform CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``) on a single-core
container share one execution unit — no overlap is physically possible and
the ratio hovers near 1.0.  The artifact therefore records ``cores`` /
``parallel_capable``, the committed baseline holds its runner's honest
ratio, and the >=1.2x acceptance floor is enforced only when
``BENCH_HETERO_REQUIRE_OVERLAP=1`` AND the host can actually parallelize
(the dedicated 2-device CI leg).  Env knobs (smoke mode):
BENCH_HETERO_REQS, BENCH_HETERO_PLEN, BENCH_HETERO_TOKENS,
BENCH_HETERO_REPS, BENCH_HETERO_INJECTS.
"""
from __future__ import annotations

import json
import os
import sys
import time
from collections import deque
from typing import Dict, List, Tuple

from repro.core.requests import Priority, Request


def bench_hetero() -> Tuple[List[dict], float]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_tiny_config
    from repro.core.engine import RealAgentXPUEngine
    from repro.models import init_params

    cfg = get_tiny_config("llama3-405b")
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n_devices = len(jax.devices())
    cores = os.cpu_count() or 1
    parallel_capable = n_devices >= 2 and cores >= 2

    n_pro = int(os.environ.get("BENCH_HETERO_REQS", "5"))
    # > HEG chunk_size (128), so every prompt prefills in several chunks
    # and decode segments of earlier flows interleave with later chunks
    plen = int(os.environ.get("BENCH_HETERO_PLEN", "160"))
    out_tokens = int(os.environ.get("BENCH_HETERO_TOKENS", "32"))
    reps = int(os.environ.get("BENCH_HETERO_REPS", "4"))
    n_inj = int(os.environ.get("BENCH_HETERO_INJECTS", "4"))
    r_plen, r_out = 16, 6
    max_len = 256

    def mk_proactive(base_id):
        # distinct prompts per flow AND per rep (seeded by base_id): no
        # shared prefixes, so every prefill is cold and in dual mode every
        # one of them stages on the prefill device — seed reuse across
        # reps would turn later reps into prefix-cache hits and quietly
        # stop measuring prefill overlap at all
        return [Request(
            id=base_id + i, priority=Priority.PROACTIVE, prompt_len=plen,
            max_new_tokens=out_tokens, arrival_time=0.0,
            tokens=np.random.default_rng(base_id + i).integers(
                0, cfg.vocab_size, (1, plen)))
            for i in range(n_pro)]

    def mk_reactive(base_id, k, arrival=0.0):
        return Request(
            id=base_id + 900 + k, priority=Priority.REACTIVE,
            prompt_len=r_plen, max_new_tokens=r_out, arrival_time=arrival,
            tokens=np.random.default_rng(base_id + 500 + k).integers(
                0, cfg.vocab_size, (1, r_plen)))

    def mk_mixed(base_id):
        # exactness trace: proactive load + reactives preempting proactive
        # prefill mid-prompt (sim arrivals inside the prefill phase) + one
        # flow repeating flow 0's prompt so its prefix hit must be served
        # from the decode pool (the co-located fallback path in dual mode)
        reqs = mk_proactive(base_id)
        reqs.append(Request(
            id=base_id + 800, priority=Priority.PROACTIVE, prompt_len=plen,
            max_new_tokens=out_tokens, arrival_time=0.003,
            tokens=np.random.default_rng(base_id).integers(
                0, cfg.vocab_size, (1, plen))))
        reqs += [mk_reactive(base_id, 0, arrival=0.0008),
                 mk_reactive(base_id, 1, arrival=0.004)]
        return reqs

    def pct_ms(vals, q):
        return float(np.percentile(vals, q)) * 1e3 if vals else None

    def run_mode(dual: bool) -> dict:
        # with one visible device dual mode is explicitly co-located — the
        # ratio then honestly measures ~1.0
        devs = jax.devices()
        eng = RealAgentXPUEngine(
            cfg, params, max_len=max_len,
            pool_slots=n_pro + max(2, n_inj) + 1,
            max_fused_steps=16, decode_segment_steps=4,
            elastic_decode=False, dual_device=dual,
            prefill_device=devs[0] if dual and len(devs) < 2 else None)
        be = eng.backend
        # warm-up: compile every shape of the measured traces (staged
        # prefill buckets + truncation + handoff in dual mode; the mixed
        # trace's join/abort/prefix-hit programs; the reactive buckets)
        eng.serve(mk_proactive(0))
        eng.serve(mk_mixed(100))
        b = 1
        while b <= 16:
            fn = be._decode_run_fn(be.pool_slots, b)
            _, be._toks, be._pool = fn(be.params, be._pool, be._toks,
                                       be._mask)
            b *= 2

        # -- overlapped vs serialized aggregate throughput (best-of-reps) --
        best_thr, best_wall = 0.0, None
        for rep in range(reps):
            trace = mk_proactive(1000 * (rep + 1))
            t0 = time.perf_counter()
            m = eng.serve(trace)
            jax.block_until_ready(be._pool)
            wall = time.perf_counter() - t0
            tokens = sum(r.decoded for r in m.completed)
            if tokens != n_pro * out_tokens:
                raise RuntimeError(
                    f"bench_hetero (dual={dual}): rep {rep} completed "
                    f"{tokens} of {n_pro * out_tokens} tokens")
            thr = tokens / max(wall, 1e-9)
            if thr > best_thr:
                best_thr, best_wall = thr, wall

        # -- byte-exactness streams from the mixed trace --------------------
        mixed = mk_mixed(5000)
        eng.serve(mixed)
        streams = {r.id - 5000: eng.output_tokens(r.id) for r in mixed}

        # -- reactive TTFT under concurrent proactive prefill ---------------
        # wall-clock injections early in the run, while the staggered
        # proactive prompts are still prefilling (the load the paper's
        # reactive-latency story is about); pooled across reps
        ttfts: List[float] = []
        for rep in range(reps):
            base = 20_000 * (rep + 1)
            tok_wall: Dict[int, list] = {}
            deadline: Dict[int, float] = {}

            def on_token(req, tok):
                tok_wall.setdefault(req.id, []).append(time.perf_counter())

            offs = [best_wall * (0.05 + 0.30 * k / max(n_inj - 1, 1))
                    for k in range(n_inj)]
            pending = deque(
                (off, mk_reactive(base, k)) for k, off in enumerate(offs))
            t_start = time.perf_counter()

            def source(now):
                out = []
                while pending and \
                        time.perf_counter() - t_start >= pending[0][0]:
                    off, r = pending.popleft()
                    deadline[r.id] = t_start + off
                    out.append((r, on_token))
                return out

            eng.set_arrival_source(source)
            for r in mk_proactive(base):
                eng.submit(r, on_token=on_token)
            t_start = time.perf_counter()
            eng.run()
            eng.set_arrival_source(None)
            ttfts.extend(tok_wall[rid][0] - t for rid, t in deadline.items()
                         if tok_wall.get(rid))
        if not ttfts:
            raise RuntimeError(
                f"bench_hetero (dual={dual}): 0 of {reps * n_inj} reactive "
                f"injections landed inside the run — shrink the offsets or "
                f"raise BENCH_HETERO_TOKENS/REQS")

        st = eng.stats()
        return {
            "mode": "dual" if dual else "single",
            "dual_active": bool(st.get("dual_device")),
            "tokens_per_s": best_thr,
            "wall_s": best_wall,
            "n_ttft_samples": len(ttfts),
            "reactive_ttft_p50_ms": pct_ms(ttfts, 50),
            "reactive_ttft_p95_ms": pct_ms(ttfts, 95),
            "staged_prefills": st.get("staged_prefills", 0),
            "handoff_device_calls": st.get("handoff_device_calls", 0),
            "kv_bytes_handoff": st.get("kv_bytes_handoff", 0),
            "colocated_hits": st.get("colocated_hits", 0),
            "co_executed_segments": st["co_executed_segments"],
            "co_execution_decode_slowdown_measured":
                st["co_execution_decode_slowdown_measured"],
            "streams": streams,
        }

    single = run_mode(False)
    dual = run_mode(True)
    token_exact = int(single.pop("streams") == dual.pop("streams"))
    ratio = dual["tokens_per_s"] / max(single["tokens_per_s"], 1e-9)
    ttft_ratio = (dual["reactive_ttft_p50_ms"] or 1e9) / \
        max(single["reactive_ttft_p50_ms"] or 1e-9, 1e-9)

    require = os.environ.get("BENCH_HETERO_REQUIRE_OVERLAP", "") \
        not in ("", "0")
    if require and not parallel_capable:
        print(f"WARNING: BENCH_HETERO_REQUIRE_OVERLAP set but host cannot "
              f"parallelize ({cores} core(s), {n_devices} device(s)) — "
              f"overlap floor NOT enforced this run", file=sys.stderr)
    if require and parallel_capable and ratio < 1.2:
        raise RuntimeError(
            f"bench_hetero: overlap_throughput_ratio {ratio:.3f} below the "
            f"1.2x acceptance floor on a parallel-capable host "
            f"({cores} cores, {n_devices} devices)")

    out = {
        "n_proactive": n_pro, "prompt_len": plen, "out_tokens": out_tokens,
        "reps": reps, "n_injections": n_inj,
        "n_devices": n_devices, "cores": cores,
        "parallel_capable": parallel_capable,
        "single": single, "dual": dual,
        "overlap_throughput_ratio": ratio,
        "reactive_ttft_ratio": ttft_ratio,
        "token_exact": token_exact,
    }
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_hetero.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, default=float)
    return [single, dual], ratio
