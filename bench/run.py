#!/usr/bin/env python3
"""On-chip benchmark of the served path: one cell, one run, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its offered rate and the limit of its
correctness check are in ``bench/cells/<cell>.json``; each metric is read
by ``bench/metrics/<metric>.py``; the configuration's ``model_type`` names
its family, ``bench/families/<model_type>.py`` (``bench/family.py``).  The
harness finds all of them by name, so a new cell, configuration, mix,
metric or model family is new files only.

In order, a run:
  1. refuses to run without a TPU, or with fewer chips than the cell asks;
  2. draws the weights on the device from ``--seed`` (``bench/model.py``,
     by the configuration's family)
     and builds the engine with ``launch/serve.py``'s ``real_engine`` from
     every engine setting the configuration states, then checks the built
     engine against them;
  3. warms every shape the mix uses through the served path
     (``ServingFrontend.submit``): one flow of each prompt length the mix
     can send, then at each KV extent it reaches half a pool of flows and
     a full pool that drains one flow at a time;
  4. offers the load: closed-loop proactive agents and open-loop reactive
     arrivals, from a pre-roll before the window to a post-roll after it
     that lasts until every flow sent in the window has finished;
  5. with ``--trace 1``, traces the window's last few seconds;
  6. frees the engine and compares a seeded sample of the finished flows
     with the float32 reference (``bench/check.py``); with ``--control 1``
     the float8 control stands in the program's place and decides
     ``correct``;
  7. prints the compared numbers beside their limits on stderr, and one
     JSON line on stdout: ``correct``, ``attempted``, ``failed``,
     ``metrics`` (end-to-end with ``--trace 0``, per-layer with
     ``--trace 1``), ``device`` and, traced, ``breakdown``, then ``checks``.

Every run logs on stderr the backend's jit keys built after the warm-up
(what compiles in the window, and when), the keys the window dispatched,
and the warmed keys it never used.

Set-up (``setup_s``) runs from process start to the window's start:
weights, compile-cache loads, warm-up and pre-roll.  JAX's persistent
compilation cache lives in ``<checkout>/.jax_cache``, so later runs of a
cell in a checkout load its programs from there.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, loadgen  # noqa: E402
from bench.trace import CLOCK_MARK  # noqa: E402

TRACE_SECONDS = 4.0  # traced span, at the window's end
DRAIN_POLL_S = 0.002  # generator poll interval
MAX_BUFFERED = 1 << 20  # tokens a flow may buffer unread (never cut off)
WARM_TOKENS = 40  # outlasts the staggered joins: full 8-step segments
OUT_DIR = os.path.join(ROOT, "bench_out")  # traces (git-ignored)


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the float8 control of the correctness check "
                         "(bench/check.py) in the program's place: its gap "
                         "decides `correct`; benchmark runs never do")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                    help="serve with this KV dtype instead of the "
                         "configuration's (to read the program's int8 KV "
                         "path against the cell's limit only)")
    ap.add_argument("--rate", type=float, default=None,
                    help="offer reactive flows at this rate instead of the "
                         "cell's (for the rate sweep only)")
    return ap.parse_args(argv)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    return cells[name]


def devices(chips: int, require_tpu: bool) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def metric_reader(name: str, metrics_dir: str):
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# configuration key -> launcher flag; every engine setting the file states
# is passed, so no launcher default decides what a cell serves
ENGINE_FLAGS = {"scheduler": "--scheduler", "kv_dtype": "--kv-dtype",
                "kernel_backend": "--kernel-backend",
                "pool_slots": "--pool-slots",
                "max_fused_steps": "--max-fused-steps",
                "decode_segment_steps": "--decode-segment-steps"}


def engine_flags(conf: dict) -> list:
    """The launcher flags of a configuration file: the settings above, a
    pool that cannot grow past its slots, and the prefix cache on or off."""
    flags = ["--real", "--full-width"]
    for key, flag in ENGINE_FLAGS.items():
        flags += [flag, str(conf[key])]
    flags += ["--pool-slots-max", str(conf["pool_slots"])]
    if not conf["prefix_cache"]:
        flags.append("--no-prefix-cache")
    return flags


def built_settings(eng) -> dict:
    """The settings of the configuration file, as the built engine has
    them: the KV dtype, kernel backend and pool from its ``stats()``, the
    rest from its attributes where it still has them (a setting the
    engine no longer exposes is not checked)."""
    b, st = eng.backend, eng.stats()
    out = {"kv_dtype": st["kv_dtype"],
           "kernel_backend": st["kernel_backend"],
           "pool_slots": st["pool_slots"],
           "pool_slots_max": st["pool_slots_max"],
           "max_len": b.max_len}
    for key, read in (
            ("scheduler", lambda: eng.scheduler_name),
            ("prefix_cache", lambda: b._prefix is not None),
            ("max_fused_steps", lambda: eng.sched_kw["max_fused_steps"]),
            ("decode_segment_steps", lambda: b.decode_segment_steps)):
        try:
            out[key] = read()
        except (AttributeError, KeyError):
            pass
    return out


def build_engine(conf: dict, cfg, params, chips: int):
    """The engine as ``launch/serve.py`` builds it from the configuration's
    settings (``engine_flags``), with the configuration's ring length.  A
    one-chip cell pins the single-device backend.  The built engine is
    checked against the file: a launcher default that changes cannot
    change what a cell serves."""
    import jax.numpy as jnp
    from repro.launch import serve
    flags = engine_flags(conf)
    if chips == 1:
        flags.append("--no-dual-device")
    args = serve.build_parser().parse_args(flags)
    ring_len = serve.ring_len
    serve.ring_len = lambda _args: int(conf["max_len"])
    try:
        eng = serve.real_engine(args, cfg, params, jnp.bfloat16
                                if conf["dtype"] == "bfloat16"
                                else jnp.float32)
    finally:
        serve.ring_len = ring_len
    want = {k: conf[k] for k in ENGINE_FLAGS}
    want.update(pool_slots_max=conf["pool_slots"], max_len=conf["max_len"],
                prefix_cache=conf["prefix_cache"])
    built = built_settings(eng)
    wrong = {k: (built[k], v) for k, v in want.items()
             if k in built and built[k] != v}
    if wrong:
        raise RuntimeError(f"the engine differs from its configuration "
                           f"(built, stated): {wrong}")
    return eng


class CompileLog:
    """The backend's jit keys: when each was first built, and how often
    each was dispatched inside the window.  Names what compiles in the
    window, and what the warm-up builds that the window never uses."""

    def __init__(self, backend):
        self.built, self.used = {}, {}
        self.window = None
        cache = getattr(backend, "_jit_cache", None)
        jitted = getattr(backend, "_jitted", None)
        if cache is None or jitted is None:  # nothing to log
            return

        def logged(key, build, donate=()):
            now = time.perf_counter()
            if key not in cache:
                self.built[key] = now
            if self.window is not None \
                    and self.window[0] <= now < self.window[1]:
                self.used[key] = self.used.get(key, 0) + 1
            return jitted(key, build, donate)
        backend._jitted = logged

    def report(self, warm_end: float) -> dict:
        ws = self.window[0] if self.window else warm_end
        late = sorted((t - ws, k) for k, t in self.built.items()
                      if t >= warm_end)
        warm = {k for k, t in self.built.items() if t < warm_end}
        return {"built_after_warm_up": [[round(t, 3), repr(k)]
                                        for t, k in late],
                "window_uses": sorted([repr(k), n]
                                      for k, n in self.used.items()),
                "warmed_unused": sorted(repr(k) for k in warm
                                        if k not in self.used)}


def counters(eng) -> dict:
    """The backend's counters (read while the engine thread runs)."""
    for _ in range(100):
        try:
            return dict(eng.stats())
        except RuntimeError:  # a dict changed size under us: read again
            time.sleep(0.001)
    return dict(eng.stats())


def kv_buckets(traffic: dict, max_len: int) -> list:
    """(kv extent, prompt length) pairs: for each power-of-two KV extent a
    decode can reach, a prompt length of the mix that starts there."""
    lengths = loadgen.prompt_lengths(traffic)
    out, k = [], 2
    while k <= max_len:
        fits = [n for n in lengths if k // 2 <= n < k]
        if fits:
            out.append((k, min(fits)))
        k *= 2
    return out


def drain_lengths(slots: int) -> list:
    """Output lengths of a full pool of warm-up flows, first submitted
    longest: once all have joined, the pool drains from its highest slot
    down, 1, 2 and 4 tokens apart in turn, so decode runs of each of those
    lengths meet the row extents a draining pool passes through."""
    out, extra = [], 0
    for i in range(slots):
        out.append(WARM_TOKENS + extra)
        extra += (1, 2, 4)[i % 3]
    return out[::-1]


def serve_all(fe, batch, timeout=900.0):
    """Serve ``(prompt, max_new)`` pairs at once and wait for them."""
    from repro.core.requests import Priority
    handles = [fe.submit(p[None], priority=Priority.PROACTIVE,
                         max_new_tokens=m) for p, m in batch]
    for h in handles:
        h.result(timeout=timeout)
    bad = [h for h in handles if h.status != "completed"]
    if bad:
        raise RuntimeError(f"warm-up flow {bad[0].flow_id} ended "
                           f"{bad[0].status}: {bad[0].fault}")


def warm_up(fe, gen: loadgen.Traffic, conf: dict, log):
    """Every prompt length the mix sends (its prefill programs), then, at
    each KV extent the mix reaches, the two row extents its window
    dispatches (half the pool and all of it, as the agents keep three
    quarters of it busy): half a pool of flows of one length, and a full
    pool that drains one flow at a time (``drain_lengths``), for each
    segment length.  Served like any traffic.  A backend that stages
    prefills on a second device takes the prompt lengths no more at a
    time than it stages at once, so each length warms its staged path."""
    lengths = loadgen.prompt_lengths(gen.spec)
    slots = int(conf["pool_slots"])
    group = min(slots, getattr(fe.engine.backend, "prefill_inflight_max",
                               slots))
    t = time.perf_counter()
    for i in range(0, len(lengths), group):  # no more than the pool holds
        serve_all(fe, [(gen.prompt(n), 2) for n in lengths[i:i + group]])
    log(f"[warm] {len(lengths)} prompt lengths {lengths[0]}..{lengths[-1]}"
        f" in {time.perf_counter() - t:.1f} s")
    for k, n in kv_buckets(gen.spec, int(conf["max_len"])):
        t = time.perf_counter()
        serve_all(fe, [(gen.prompt(n), WARM_TOKENS)
                       for _ in range(max(slots // 2, 1))])
        serve_all(fe, [(gen.prompt(n), m) for m in drain_lengths(slots)])
        log(f"[warm] kv extent {k}: {slots // 2} and {slots} flows of {n} "
            f"tokens in {time.perf_counter() - t:.1f} s")


def flow_record(f: loadgen.Flow) -> dict:
    """What a flow sent and got back; a completed flow with fewer tokens
    than it asked for is ``short``."""
    h = f.handle
    out = list(h.tokens_out)
    status = h.status
    if status == "completed" and len(out) != f.max_new:
        status = "short"
    return {"kind": f.kind, "prompt": f.prompt, "max_new": f.max_new,
            "intended": f.intended, "sent": f.sent,
            "in_window": f.in_window, "status": status, "out": out,
            "walls": list(h.token_walls),
            "preempted": h.req.preempt_count > 0}


def backlog(flows) -> int:
    """Reactive flows sent that have no first token yet."""
    return sum(f.kind == "reactive" and not f.handle.token_walls
               and f.handle.status is None for f in flows)


def drive(fe, eng, gen: loadgen.Traffic, seconds: float, drain_s: float,
          tracer=None, keys=None, log=print) -> dict:
    """Offer the load; returns the run's flows, window and counters."""
    from repro.core.requests import Priority
    t0 = time.perf_counter()
    ws, we = t0 + gen.preroll_s, t0 + gen.preroll_s + seconds
    if tracer is not None:
        tracer.arm(ws, we)
    if keys is not None:
        keys.window = (ws, we)
    flows, agent_flow = [], [None] * gen.agents
    snap = {}
    next_reactive = 0

    def send(f: loadgen.Flow, now: float, intended: float):
        f.intended, f.sent = intended, now
        f.in_window = ws <= intended < we
        prio = Priority.REACTIVE if f.kind == "reactive" \
            else Priority.PROACTIVE
        f.handle = fe.submit(f.prompt[None], priority=prio,
                             max_new_tokens=f.max_new)
        flows.append(f)

    while True:
        now = time.perf_counter()
        if "start" not in snap and now >= ws:
            snap["start"] = counters(eng)
            snap["backlog_start"] = backlog(flows)
        if "end" not in snap and now >= we:
            snap["end"] = counters(eng)
            snap["backlog_end"] = backlog(flows)
        if tracer is not None:
            tracer.tick(now)
        if now >= we and (now > we + drain_s or all(
                f.handle.status is not None for f in flows if f.in_window)):
            break
        # closed loop: an agent sends as soon as its last flow finished,
        # or a while after a refusal (a client backs off, never storms)
        for a in range(gen.agents):
            f = agent_flow[a]
            if f is not None and f.handle.status is None:
                continue
            if f is not None and f.handle.status == "rejected":
                if f.retry_at is None:
                    f.retry_at = now + gen.retry_after_s
                if now < f.retry_at:
                    continue
            nf = gen.next_proactive(a)
            # due when the agent's previous reply ended
            due = f.handle.token_walls[-1] if f is not None \
                and f.handle.status == "completed" and f.handle.token_walls \
                else now
            send(nf, now, min(due, now))
            agent_flow[a] = nf
        # open loop: reactive flows at their due times
        while next_reactive < len(gen.reactive) \
                and t0 + gen.reactive[next_reactive].due <= now:
            f = gen.reactive[next_reactive]
            send(f, now, t0 + f.due)
            next_reactive += 1
        nxt = t0 + gen.reactive[next_reactive].due \
            if next_reactive < len(gen.reactive) else now + DRAIN_POLL_S
        time.sleep(max(min(nxt - time.perf_counter(), DRAIN_POLL_S), 0.0))
    # the window's flows are in; cancel what only the post-roll sent
    for f in flows:
        if f.handle.status is None:
            f.handle.cancel()
    fe.close(timeout=300)
    log(f"[load] {len(flows)} flows sent; reactive sent "
        f"{next_reactive} of {len(gen.reactive)} scheduled; reactive "
        f"backlog {snap.get('backlog_start')} at the window's start, "
        f"{snap.get('backlog_end')} at its end")
    records = [flow_record(f) for f in flows]
    for f in flows:  # handles reach the engine: let it be freed
        f.handle = None
    return {"flows": records, "ws": ws, "we": we, "counters": snap}


class Tracer:
    """Traces the window's last ``TRACE_SECONDS``.  Stopping the profiler
    blocks the process for seconds while it collects the trace, so it
    stops when the window closes: no flow of the window is sent late for
    it.  ``host`` holds the host clock at a mark just after the trace
    starts and one just before it stops."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.state = "idle"
        self.host = None

    def arm(self, ws: float, we: float):
        self.t_on = we - min(TRACE_SECONDS, (we - ws) / 2)
        self.t_off = we

    def tick(self, now: float):
        import jax
        if self.state == "idle" and now >= self.t_on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # tracing Python would slow it
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.host = [self.mark(), None]
            self.state = "on"
        elif self.state == "on" and now >= self.t_off:
            self.host[1] = self.mark()
            jax.profiler.stop_trace()
            self.state = "done"

    @staticmethod
    def mark() -> float:
        """The host clock, read inside a host span the trace keeps
        (``CLOCK_MARK``): the trace's clock maps onto the host's by it."""
        import jax
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            return time.perf_counter()


def label_hooks(eng):
    """Host spans around the engine's event loop and the backend hooks
    its scheduler calls, so an idle gap names what the host was doing."""
    import jax
    hooks = [(eng, "run")] + [(eng.backend, n) for n in (
        "prefill_chunk", "prefill_done", "decode_run", "decode_iteration",
        "finish")]
    for backend, name in hooks:
        fn = getattr(backend, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _label=f"bench:{type(backend).__name__}."
                    f"{name}", **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)
        setattr(backend, name, wrapped)


def memory(devs) -> dict:
    peak, limit = 0, 0
    for d in devs:
        st = d.memory_stats() or {}
        if st.get("peak_bytes_in_use", 0) >= peak:
            peak = st.get("peak_bytes_in_use", 0)
            limit = st.get("bytes_limit", 0)
    return {"peak_bytes": int(peak), "bytes_limit": int(limit)}


def prepare(args, bench: dict, data_dir: str, require_tpu: bool, log):
    """Everything before the load: the chip check, the configuration, the
    weights, the engine and its warm-up."""
    cell = load_cell(args.workload, bench)
    chips = int(cell["chips"])
    devs = devices(chips, require_tpu)
    import jax
    from repro.launch.compile_cache import (CompileStats,
                                            configure_compile_cache)
    from repro.launch.frontend import ServingFrontend

    from bench import model
    if require_tpu:
        configure_compile_cache()
    compiles = CompileStats()
    conf = model.load_config(cell["config"],
                             os.path.join(data_dir, "configs"))
    if args.kv_dtype is not None:
        conf["kv_dtype"] = args.kv_dtype
    traffic = read_json(os.path.join(data_dir, "traffic",
                                     f"{cell['traffic']}.json"))
    cell_file = read_json(os.path.join(data_dir, "cells",
                                       f"{cell['name']}.json"))
    peaks_all = read_json(os.path.join(BENCH_DIR, "peaks.json"))
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks_all:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    cfg = model.model_config(conf, data_dir)
    dtype = jax.numpy.bfloat16 if conf["dtype"] == "bfloat16" \
        else jax.numpy.float32
    key = jax.random.PRNGKey(int(loadgen.seed_rng(args.seed, "weights")
                                 .integers(0, 2 ** 31 - 1)))
    params = model.init_weights(cfg, key, dtype)
    jax.block_until_ready(params)
    log(f"[setup] {cfg.name}: {cfg.num_layers} layers, d {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads x {cfg.head_dim}, ffn "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; weights in "
        f"{time.perf_counter() - T_START:.1f} s since start")
    eng = build_engine(conf, cfg, params, chips)
    log(f"[setup] backend {type(eng.backend).__name__}, dual device "
        f"{eng.stats().get('dual_device', False)}")
    keys = CompileLog(eng.backend)
    fe = ServingFrontend(eng, max_buffered_tokens=MAX_BUFFERED).start()
    rate = cell_file["reactive_rate_per_s"] if args.rate is None \
        else args.rate
    agents = loadgen.agents_for(traffic, int(conf["pool_slots"]))
    gen = loadgen.Traffic(traffic, rate, agents, cfg.vocab_size, args.seed,
                          args.seconds)
    warm_up(fe, gen, conf, log)
    c_warm = compiles.snapshot()
    t_warm = time.perf_counter()
    log(f"[setup] warm: {c_warm['compiles']} compiles "
        f"({c_warm['fresh_compiles']} fresh, {c_warm['cache_hits']} from "
        f"the cache, {c_warm['compile_seconds']:.1f} s); {agents} agents, "
        f"reactive {rate}/s; load starts "
        f"{time.perf_counter() - T_START:.1f} s after process start")
    return SimpleNamespace(cell=cell, chips=chips, devs=devs, conf=conf,
                           traffic=traffic, cell_file=cell_file,
                           peaks=peaks_all.get(kind), kind=kind, cfg=cfg,
                           params=params, eng=eng, fe=fe, gen=gen,
                           agents=agents, compiles=compiles, c_warm=c_warm,
                           keys=keys, t_warm=t_warm)


def run(args, *, bench: dict = None, data_dir: str = BENCH_DIR,
        require_tpu: bool = True, log=None) -> dict:
    """One run of one cell; returns the result object (``main`` prints
    it).  The CPU tests pass their own ``bench`` and ``data_dir`` (which
    holds ``configs/``, ``traffic/``, ``cells/`` and may hold
    ``families/``) and
    ``require_tpu=False`` to drive a run at a tiny size."""
    import jax
    import numpy as np
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    if bench is None:
        bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    ctx = prepare(args, bench, data_dir, require_tpu, log)
    cell, conf, traffic, cfg, params = (ctx.cell, ctx.conf, ctx.traffic,
                                        ctx.cfg, ctx.params)
    eng, fe, gen = ctx.eng, ctx.fe, ctx.gen
    ctx.eng = ctx.fe = ctx.gen = None  # the engine is freed before the check
    tracer = None
    trace_dir = os.path.join(OUT_DIR, f"trace-{cell['name']}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        label_hooks(eng)
        tracer = Tracer(trace_dir)
    rec = drive(fe, eng, gen, args.seconds, float(traffic["drain_limit_s"]),
                tracer=tracer, keys=ctx.keys, log=log)
    setup_s = rec["ws"] - T_START
    rec["compiles_total"] = ctx.compiles.snapshot()
    mem = memory(ctx.devs)
    del fe, eng, gen
    gc.collect()

    # -- correctness --------------------------------------------------------
    window_flows = [f for f in rec["flows"] if f["in_window"]]
    picked = check.sample(window_flows, args.seed)
    t = time.perf_counter()
    res = check.compare(cfg, params, picked, control=bool(args.control))
    log(f"[check] {res['flows']} flows, {res['tokens']} served tokens "
        f"(distinct share {res['distinct_share']:.3f}) against the float32 "
        f"reference in {time.perf_counter() - t:.1f} s")
    limit = float(ctx.cell_file["widest_gap_limit"])
    short = sum(f["status"] == "short" for f in window_flows)
    # with --control 1 the control stands in the program's place
    gap_name, gap = ("control_widest_logit_gap", res["control_widest_gap"]) \
        if args.control else ("widest_logit_gap", res["widest_gap"])
    if args.control:
        log(f"[check] the program's own widest gap, not compared: "
            f"{res['widest_gap']}")
    checks = {gap_name: {"value": gap, "limit": limit},
              "short_flows": {"value": short, "limit": 0},
              "sampled_tokens": {"value": res["tokens"],
                                 "limit": check.MIN_TOKENS}}
    correct = (gap <= limit and short == 0
               and res["tokens"] >= check.MIN_TOKENS)

    # -- metrics ------------------------------------------------------------
    failed = sum(f["status"] != "completed" for f in window_flows)
    rec.update(seconds=args.seconds, setup_s=setup_s, cfg=cfg, conf=conf,
               peaks=ctx.peaks, memory=mem, traffic=traffic, trace=None,
               trace_host=None)
    device = {"platform": ctx.devs[0].platform, "kind": ctx.kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem["peak_bytes"]}
    breakdown = None
    if args.trace:
        from bench import trace as tr
        path = tr.find_xplane(trace_dir)
        if path is not None:
            raw = tr.load(path)
            red = tr.reduce(raw, ctx.chips)
            rec["trace"], rec["trace_host"] = red, tracer.host
            if len(red["marks_ns"]) == 2:
                drift = (red["marks_ns"][1] - red["marks_ns"][0]) / 1e9 \
                    - (tracer.host[1] - tracer.host[0])
                log(f"[trace] {red['window_s']:.3f} s traced; the trace's "
                    f"clock and the host's differ by {drift * 1e3:.3f} ms "
                    f"between the marks")
            # averaged over the devices the cell's program used: a cell
            # may hold chips on which nothing runs
            busy = [d["busy_s"] for d in red["devices"] if d["busy_s"] > 0]
            device["busy_s"] = float(np.mean(busy)) if busy else 0.0
            device["window_s"] = red["window_s"]
            d0 = red["devices"][0] if red["devices"] else {"ops": [],
                                                           "gaps": []}
            breakdown = {"device_ops": [[n, s] for n, s in d0["ops"][:10]],
                         "idle_gaps": [[n, s] for n, s in d0["gaps"][:10]]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in bench[group]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = metric_reader(m["name"], os.path.join(BENCH_DIR,
                                                      "metrics"))(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(window_flows),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    log(f"[keys] {json.dumps(ctx.keys.report(ctx.t_warm))}")
    log(f"[run] setup {setup_s:.1f} s, window {args.seconds} s, "
        f"{len(window_flows)} flows in the window, {failed} failed; "
        f"compiles after warm-up: "
        f"{rec['compiles_total']['compiles'] - ctx.c_warm['compiles']}; "
        f"peak {mem['peak_bytes'] / 2**30:.2f} GiB of "
        f"{mem['bytes_limit'] / 2**30:.2f} GiB")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        out = run(args)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
