"""Pluggable execution backends (DESIGN.md §2, §6).

The scheduler is execution-agnostic: it announces *kernel completions* in
simulated-clock order and an ``ExecutionBackend`` decides what (if anything)
actually runs.  Two implementations:

  SimBackend      pure timing study — every hook is a no-op.  This module
                  deliberately imports no JAX so the simulation-only path
                  (``AgentXPUEngine.run_trace``) stays JAX-free.
  JaxRealBackend  real token generation on a device-resident slot-pool KV
                  cache: all inference callables donate their pool buffers
                  (in-place update, no per-call copy), per-slot last tokens
                  and the batch mask live on device, scheduler-announced
                  fused runs execute many decode iterations as one jitted
                  ``lax.scan`` with a single host sync at the boundary, and
                  every decode dispatch is elastic in both axes — bounded
                  to the pow-2 live rows and live KV prefix (DESIGN.md §9).

Hook protocol (driven by ``SchedulerBase.on_complete`` — no monkeypatching):

    register(req, on_token)         request submitted (streaming callback,
                                    prompt tokens uploaded to device once)
    prefill_chunk(req, start, n)    all kernels of one prompt chunk done
                                    (first chunk allocates the pool slot:
                                    slot lifetime starts at prefill START)
    prefill_done(req)               prefill complete -> first token emitted
    decode_run(reqs, n_steps)       scheduler guarantees the decode batch is
                                    membership-stable for n_steps iterations
                                    (the event horizon) -> fused execution
                                    in bounded abortable segments
    request_preempt(now)            a reactive arrival / prefill join
                                    truncated the plan -> cancel unlaunched
                                    segments at a kernel boundary
    decode_iteration(reqs)          one batched decode iteration committed
                                    (replays from the fused block if present)
    finish(req)                     request done -> free its slot
"""
from __future__ import annotations

import heapq
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from repro.core.contention import (MemoryPressureEstimator,
                                   co_execution_rates)
from repro.core.faults import (AllocationFault, FaultError, FaultInjector,
                               FlowFault, InvariantViolation,
                               PermanentDeviceFault, TransientDeviceFault)
from repro.core.prefixcache import (PrefixCache, prefix_reuse_supported)
from repro.core.requests import Priority, Request
from repro.core.tracing import NO_PHASES, Phases

TokenCallback = Callable[[Request, int], None]


def _prompt_key(req: Request) -> tuple:
    """Token-ID key of a request's prompt (the exactness currency of the
    prefix index): a hit is only ever claimed on exact token equality."""
    import numpy as np
    return tuple(int(t) for t in
                 np.asarray(req.tokens).reshape(-1)[:req.prompt_len])


class ExecutionBackend:
    """Interface the scheduler drives through kernel-completion hooks."""

    # spans and phase clocks of the threads that drive the backend
    # (core/tracing.py); none where nothing runs on a device
    phases = NO_PHASES

    def register(self, req: Request,
                 on_token: Optional[TokenCallback] = None) -> None:
        pass

    def prefix_hit(self, req: Request) -> int:
        """Longest reusable cached-prefix length for this request's prompt.

        Consulted by the scheduler at ARRIVAL (before prefill kernels are
        built), so a hit shrinks the request's prefill ETC and every
        downstream estimate — piggyback horizons, HEG kernel timing — sees
        only the real remaining tail.  0 = cold prefill."""
        return 0

    def prefill_chunk(self, req: Request, seq_start: int, tokens: int,
                      now: float) -> None:
        pass

    def prefill_done(self, req: Request, now: float) -> None:
        pass

    def decode_run(self, reqs: List[Request], n_steps: int,
                   now: float) -> None:
        """Scheduler announcement: the coming ``n_steps`` decode iterations
        will run with exactly this membership (no arrival/completion/finish
        can change the batch before they commit)."""
        pass

    def request_preempt(self, now: float) -> None:
        """Scheduler notice that a higher-priority event (reactive arrival,
        prefill join) truncated the announced run: cancel every decode
        segment not yet launched.  Already-produced tokens stay buffered —
        the scheduler still commits them via ``decode_iteration`` (the
        truncated plan's remaining replay steps)."""
        pass

    def decode_iteration(self, reqs: List[Request], now: float) -> None:
        pass

    def finish(self, req: Request, now: float) -> None:
        pass

    def release(self, reqs: List[Request], now: float) -> None:
        pass

    # -- failure model (DESIGN.md §12) ---------------------------------------
    def deadline_expired(self, req: Request, now: float) -> bool:
        """True once ``req`` has overrun its (relative) deadline; consulted
        by the scheduler's per-turn poll.  The flow is then aborted at the
        next segment boundary with the ``timed_out`` terminal status."""
        return req.deadline is not None \
            and now - req.arrival_time > req.deadline

    def take_flow_faults(self) -> List[FlowFault]:
        """Drain flow-attributable failures parked since the last poll
        (hook exception, allocation failure, flow-targeted device fault).
        The scheduler quarantines each envelope's flow as ``failed``."""
        return []

    def quarantine_flow(self, req: Request, now: float) -> None:
        """Retire ONE failed/expired flow's execution state — slot, donor
        refcounts, prefix pins — while keeping every other flow's committed
        run (buffered replay rows included) intact."""
        self.finish(req, now)

    def evict_prefix_leaves(self) -> int:
        """Degradation-ladder rung 1: force-evict unpinned prefix-cache
        leaves; returns the number of off-pool KV rows freed."""
        return 0

    def kv_store_rows(self) -> int:
        """Off-pool KV rows held by the prefix snapshot store (counted as
        row-equivalents by admission occupancy)."""
        return 0

    def validate(self, strict: bool = False) -> List[str]:
        """Audit internal accounting invariants; returns the violations
        found (empty = clean).  ``strict=True`` raises
        ``InvariantViolation`` instead of returning them."""
        return []

    def output_tokens(self, req_id: int) -> list:
        return []

    def stats(self) -> dict:
        return {}


class SimBackend(ExecutionBackend):
    """Timing-only backend: the discrete-event simulator is the execution.

    It still models shared-prefix hit accounting (DESIGN.md §10) with the
    SAME radix index, driven at the SAME scheduler instants as the real
    backend — match at arrival, insert at prefill completion, pin while in
    flight — so sim and real traces stay equal with the cache on or off.
    ``max_len`` mirrors the real backend's ring capacity (its wrap gate:
    a donor whose row could wrap past ``max_len`` is never indexed, since
    wrap would overwrite the donated prefix); ``None`` leaves insertion
    ungated for pure-sim studies."""

    name = "sim"

    def __init__(self, *, prefix_cache: bool = True,
                 prefix_cache_tokens: Optional[int] = None,
                 prefix_block: int = 1, max_len: Optional[int] = None):
        from repro.core.prefixcache import DEFAULT_CAPACITY_TOKENS
        self._prefix: Optional[PrefixCache] = PrefixCache(
            prefix_cache_tokens or DEFAULT_CAPACITY_TOKENS,
            block=prefix_block) if prefix_cache else None
        self.max_len = max_len
        self._hit_node: Dict[int, object] = {}
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0

    def prefix_hit(self, req: Request) -> int:
        if self._prefix is None or req.tokens is None:
            return 0
        self.prefix_prompt_tokens += req.prompt_len
        hit, node = self._prefix.match(_prompt_key(req),
                                       max_hit=req.prompt_len - 1)
        if hit <= 0 or node is None:
            return 0
        old = self._hit_node.pop(req.id, None)
        if old is not None:  # re-arrival of the same id: drop the stale pin
            self._prefix.unpin(old)
        self._prefix.pin(node)
        self._hit_node[req.id] = node
        self.prefix_hits += 1
        self.prefix_hit_tokens += hit
        return hit

    def prefill_done(self, req: Request, now: float) -> None:
        if self._prefix is None or req.tokens is None:
            return
        if self.max_len is not None \
                and req.prompt_len + req.max_new_tokens > self.max_len:
            return  # wrap gate (mirrors JaxRealBackend)
        self._prefix.insert(_prompt_key(req))

    def finish(self, req: Request, now: float) -> None:
        node = self._hit_node.pop(req.id, None)
        if node is not None and self._prefix is not None:
            self._prefix.unpin(node)

    def release(self, reqs: List[Request], now: float) -> None:
        for r in reqs:
            self.finish(r, now)

    def evict_prefix_leaves(self) -> int:
        # drive the SAME index operation as the real backend so the
        # admission ladder mutates sim and real prefix state identically;
        # the sim holds no physical KV, so 0 rows are freed
        if self._prefix is not None:
            self._prefix.evict_unpinned()
        return 0

    def validate(self, strict: bool = False) -> List[str]:
        problems: List[str] = []
        if self._prefix is not None:
            want: Dict[int, int] = {}
            for node in self._hit_node.values():
                want[id(node)] = want.get(id(node), 0) + 1
            for rid, node in self._hit_node.items():
                if node.refs < want[id(node)]:
                    problems.append(
                        f"prefix pin undercount: node {node.nid} refs "
                        f"{node.refs} < {want[id(node)]} pinning flows")
        if strict and problems:
            raise InvariantViolation("; ".join(problems))
        return problems

    def stats(self) -> dict:
        out = {"prefix_hits": self.prefix_hits,
               "prefix_hit_tokens": self.prefix_hit_tokens,
               "prefix_hit_rate": self.prefix_hit_tokens
               / max(self.prefix_prompt_tokens, 1)}
        if self._prefix is not None:
            out.update(self._prefix.stats())
        return out


def _pow2_buckets(n: int) -> List[int]:
    """Descending power-of-2 decomposition of a chunk length (96 -> [64, 32]):
    any chunk is covered by O(log n) jit-compiled shapes instead of one
    compilation per distinct (request, chunk) shape."""
    out, b = [], 1
    if n <= 0:
        return out
    while b * 2 <= n:
        b *= 2
    while n > 0:
        while b > n:
            b //= 2
        out.append(b)
        n -= b
    return out


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _launch_meta(key: tuple) -> dict:
    """Span metadata of a program's launches, from its jit key: the kind,
    and the rows, KV extent and steps or tokens where the key has them."""
    kind = key[0]
    if kind == "decode":
        return {"kind": kind, "rows": key[2], "kv_limit": key[3], "steps": 1}
    if kind == "decode_run":
        return {"kind": kind, "rows": key[3], "kv_limit": key[4],
                "steps": key[2]}
    if kind == "prefill_chunk":
        return {"kind": kind, "tokens": sum(key[2]), "kv_limit": key[4]}
    return {"kind": kind}


def decode_program(cfg, kernel_backend: str, max_len: int, pool_size: int,
                   rows: int, kv_limit: int, n_steps: Optional[int] = None):
    """The body of a pooled decode program as the backend jits it, with the
    pool and the token vector donated (arguments 1 and 2):
    ``fn(params, pool, toks, mask)`` runs one ``decode_step`` (``n_steps``
    None) or an ``n_steps`` ``decode_run`` over pool rows ``[0, rows)``,
    reading ring windows of ``kv_limit`` slots from the whole pool, and
    returns ``(next tokens | token block, token vector, pool)``.  Built
    from the configuration alone, so a compile check can build it from
    shapes."""
    import jax.numpy as jnp
    from repro.models import decode_run, decode_step
    kw = dict(kv_limit=kv_limit, full_alloc=max_len,
              kernel_backend=kernel_backend)

    def fn(params, pool, toks, mask):
        t, m = toks[:rows], mask[:rows]
        if n_steps is None:
            out, _, pool = decode_step(cfg, params, pool, t, m, **kw)
            t = jnp.where(m, out, t)
        else:
            out, t, pool = decode_run(cfg, params, pool, t, m, n_steps, **kw)
        return out, (toks.at[:rows].set(t) if rows < pool_size else t), pool
    return fn


# phase clocks that time a decode segment: its launches (with any first
# compile) and the fetch of its token block
_SEGMENT_CLOCKS = ("host_launch_s", "host_compile_s", "host_fetch_s")


class JaxRealBackend(ExecutionBackend):
    """Real execution on a device-resident slot-pool KV cache.

    Prefill is *in-pool and zero-copy* (DESIGN.md §7): the pool slot is
    allocated at prefill START, the reused row is invalidated in place
    (``kvcache.reset_row`` — slot_pos mask flip, not a KV rewrite), and
    every pow-2 bucketed sub-chunk runs ``models.extend_row`` against the
    donated pool, so prompt KV is written exactly once, straight into the
    live row.  Prompt tokens are uploaded once at ``register`` (pow-2
    padded) and sliced on device per sub-chunk; the first output token is
    fetched in ONE host sync at ``prefill_done``.  ``in_pool_prefill=False``
    preserves the previous flow — per-request B=1 scratch cache, per-chunk
    host token uploads, and a full-row ``write_slot`` bind scatter at
    ``prefill_done`` — as the measurable baseline.  Decode state —
    the KV pool, each slot's last emitted token, and the active-slot mask —
    stays on device between scheduler events:

    * every jitted inference callable donates its cache/pool (and token
      state) arguments, so the pool is updated in place instead of copied
      per call;
    * host -> device traffic is reduced to small jitted scatter updates when
      a slot binds/frees or the batch membership changes;
    * a scheduler-announced ``decode_run(reqs, n_steps)`` executes as O(log
      n_steps) jitted ``lax.scan`` programs (pow-2 run lengths), and the
      resulting ``(n_steps, pool)`` token block is fetched to host ONCE;
      subsequent ``decode_iteration`` hooks replay tokens from the block, so
      per-token ``on_token`` callbacks and output bookkeeping still happen
      at the simulated-clock instant of each iteration.

    The pool doubles (one recompilation) if demand ever exceeds the initial
    slot count; growth rebuilds all donated buffers from fresh arrays.
    """

    name = "jax"

    _ENC_DEC_MSG = (
        "JaxRealBackend cannot serve encoder-decoder configs: slot rebinding "
        "invalidates a pool row with kvcache.reset_row, which deliberately "
        "leaves enc_out / cross-attention state untouched — a rebound slot "
        "would silently serve the PREVIOUS occupant's encoder output as its "
        "cross-attention context")

    def __init__(self, cfg, params, *, pool_slots: int, max_len: int = 512,
                 dtype=None, device_resident: bool = True,
                 in_pool_prefill: Optional[bool] = None,
                 abortable_runs: bool = True,
                 decode_segment_steps: int = 8,
                 elastic_decode: bool = True,
                 prefix_cache: bool = True,
                 prefix_cache_tokens: Optional[int] = None,
                 prefix_block: int = 1,
                 kv_dtype: str = "bf16",
                 kernel_backend: str = "xla",
                 pool_slots_max: Optional[int] = None,
                 isolate_flow_faults: bool = True,
                 faults: Optional[FaultInjector] = None,
                 device_fault_retries_max: int = 3):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.models import init_cache, kv_supports_int8
        if cfg.is_encoder_decoder:
            raise NotImplementedError(self._ENC_DEC_MSG)
        if cfg.frontend != "none":
            raise NotImplementedError(
                "JaxRealBackend serves text-only decoders")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8': {kv_dtype}")
        if kernel_backend not in ("xla", "pallas"):
            raise ValueError(
                f"kernel_backend must be 'xla' or 'pallas': {kernel_backend}")
        if kv_dtype == "int8" and not kv_supports_int8(cfg):
            raise NotImplementedError(
                "int8 KV quantization needs the per-(slot, kv head) k/v ring "
                "layout; MLA configs cache a headless latent")
        if kernel_backend == "pallas" and cfg.use_mla:
            raise NotImplementedError(
                "the Pallas kernels cover the standard GQA decode/prefill "
                "path; absorbed-MLA attention has no kernel yet")
        # kv_dtype="bf16" means UNQUANTIZED — the ring stores the cache
        # compute dtype verbatim (the exactness baseline, DESIGN.md §11);
        # "int8" switches the k/v ring payload to symmetric int8 with
        # per-(slot, kv head) f32 scales.
        self.kv_dtype = kv_dtype
        self.kernel_backend = kernel_backend
        self._kv_dtype_arg = "int8" if kv_dtype == "int8" else None
        self._jax, self._jnp, self._np = jax, jnp, np
        self.cfg = cfg
        self.params = params
        # device_resident=False restores the pre-donation hot path (no buffer
        # donation, per-iteration host rebuild + upload of the batch state,
        # no fused runs) — kept as the measurable baseline of
        # benchmarks.figures.bench_decode_throughput's perf trajectory
        self.device_resident = device_resident
        # in_pool_prefill=False restores the scratch-cache + bind-scatter
        # prefill (double KV write) — the measurable baseline of
        # benchmarks.figures.bench_prefill_throughput (BENCH_prefill.json).
        # The default follows device_resident: in-pool prefill leans on
        # donation (without it every sub-chunk would copy the whole pool),
        # and the legacy baseline predates in-pool prefill anyway.
        self.in_pool_prefill = device_resident if in_pool_prefill is None \
            else in_pool_prefill
        # abortable_runs=False restores PR 2's eager fused execution (the
        # whole announced run launches as one blocking device program chain
        # at announce time) — the measurable baseline of BENCH_reactive.json.
        # Abortable mode executes the run LAZILY in bounded segments of
        # ``decode_segment_steps`` iterations: one segment launches at
        # announce, the next only when the replay buffer drains, so between
        # any two segments the host is back in the scheduler loop and a
        # ``request_preempt`` can cancel everything not yet launched at a
        # kernel boundary (DESIGN.md §8).
        self.abortable_runs = abortable_runs
        self.decode_segment_steps = max(int(decode_segment_steps), 1)
        # elastic_decode=False restores the full-pool decode dispatch (every
        # iteration computes all pool rows over the whole max_len ring) —
        # the measurable baseline of the decode-scaling sweep in
        # BENCH_decode.json.  Elastic dispatch (DESIGN.md §9) bounds each
        # decode program to the leading pow-2 live rows and the pow-2 live
        # KV prefix; it leans on donation-through-views, so legacy
        # device_resident=False implies full-pool too.
        self.elastic_decode = bool(elastic_decode) and device_resident
        self.max_len = max_len
        self.dtype = dtype or jnp.float32
        # bounded-resource failure model (DESIGN.md §12): a hard KV budget
        # (``pool_slots_max`` caps ``_grow_pool``; exhaustion is a typed
        # ``AllocationFault``, never silent growth), per-flow fault
        # quarantine (``isolate_flow_faults=False`` restores raise-out),
        # and a deterministic fault-injection seam (``core.faults``)
        self.pool_slots_max = None if pool_slots_max is None \
            else max(int(pool_slots_max), 1)
        self.isolate_flow_faults = bool(isolate_flow_faults)
        self._faults = faults
        self._fault_retry_max = max(int(device_fault_retries_max), 0)
        self._pending_faults: List[FlowFault] = []
        self._quarantined: set = set()  # rids faulted, awaiting quarantine
        self.device_fault_retries = 0  # transient launch failures retried
        self.flow_faults = 0  # flow-attributable failures recorded
        self.quarantined_flows = 0
        self.pressure_evicted_nodes = 0  # ladder rung 1 eviction victims
        self.pool_slots = max(int(pool_slots), 1)
        if self.pool_slots_max is not None:
            self.pool_slots = min(self.pool_slots, self.pool_slots_max)
        self._pool = init_cache(cfg, params, self.pool_slots, max_len,
                                self.dtype, kv_dtype=self._kv_dtype_arg)
        # min-heap: rebinding always takes the LOWEST free slot, so the live
        # high-water mark (and with it the elastic row bound) stays minimal
        self._free: List[int] = list(range(self.pool_slots))
        self._slot: Dict[int, int] = {}  # req id -> pool slot
        self._slot_pos: Dict[int, int] = {}  # pool slot -> live row position
        self._scratch: Dict[int, object] = {}  # req id -> B=1 prefill cache
        self._scratch_pos: Dict[int, int] = {}
        self._first: Dict[int, int] = {}  # first token (from last chunk)
        self._last: Dict[int, int] = {}  # host mirror of last emitted token
        self._texts: Dict[int, list] = {}
        self._on_token: Dict[int, TokenCallback] = {}
        # in-pool prefill state: device-resident prompt tokens (uploaded once
        # at register, pow-2 padded), per-request row progress, and the
        # not-yet-fetched first-token device scalar of a finished prefill
        self._tok_dev: Dict[int, object] = {}
        self._row_pos: Dict[int, int] = {}
        self._nxt_dev: Dict[int, object] = {}
        # KV-traffic accounting (BENCH_prefill.json): bytes one prompt token
        # adds to a B=1 cache, and the bytes a full-row bind scatter moves.
        # eval_shape: count bytes from abstract shapes, no device allocation.
        from repro.models import cache_bytes

        def _bytes(one_max_len):
            return cache_bytes(jax.eval_shape(
                lambda: init_cache(cfg, params, 1, one_max_len, self.dtype,
                                   kv_dtype=self._kv_dtype_arg)))
        self._kv_token_bytes = _bytes(1) - _bytes(0)
        self._bind_row_bytes = _bytes(max_len)
        # quantization-scale overhead of the resident pool (payload bytes are
        # what kv_bytes_* already count; the scales are the int8 storage tax)
        self.quant_scale_bytes = sum(
            l.size * l.dtype.itemsize for p, l in
            jax.tree_util.tree_leaves_with_path(jax.eval_shape(
                lambda: init_cache(cfg, params, self.pool_slots, max_len,
                                   self.dtype, kv_dtype=self._kv_dtype_arg)))
            if any(getattr(k, "key", None) in ("k_scale", "v_scale")
                   for k in p))
        # device-resident batch state (DESIGN.md §6): last token per slot and
        # the current iteration's membership mask, mutated only by small
        # jitted scatters / the decode calls themselves
        self._toks = jnp.zeros((self.pool_slots,), jnp.int32)
        self._mask = jnp.zeros((self.pool_slots,), bool)
        self._mask_host = np.zeros((self.pool_slots,), bool)  # mirror
        # fused-run replay buffer: host token block + committed membership.
        # _fused_left counts announced iterations NOT yet executed on device
        # (abortable mode launches them segment-by-segment on demand).
        self._fused_rows: Deque = deque()
        self._fused_slots: Optional[frozenset] = None
        self._fused_left = 0
        self._jit_cache: Dict[tuple, object] = {}
        # jitted program -> (jit key, its launch spans' metadata), and the
        # programs launched at least once (a first launch compiles)
        self._programs: Dict[object, tuple] = {}
        self._launched: set = set()
        # host spans and exclusive phase clocks (core/tracing.py)
        self.phases = Phases()
        # counters (reported by examples/ and asserted by tests/test_backend)
        self.jit_compilations = 0
        self.decode_device_calls = 0
        self.prefill_device_calls = 0
        self.host_syncs = 0  # device->host token fetches
        self.fused_steps = 0  # decode iterations served from fused runs
        self.fused_runs = 0
        self.decode_segments = 0  # lax.scan segments launched (>= runs)
        self.aborted_runs = 0  # runs truncated by request_preempt
        self.aborted_steps = 0  # announced iterations cancelled unlaunched
        self.prefill_host_syncs = 0  # first-token fetches (1 per prefill)
        self.bind_device_calls = 0  # full-row bind scatters (0 in-pool)
        self.kv_bytes_prefill = 0  # prompt-phase KV bytes written
        # elastic decode accounting (DESIGN.md §9): decode_rows and
        # decode_kv_limit are gauges of the LAST decode dispatch only (not
        # totals); kv_bytes_decode is the cumulative KV bytes decode
        # programs streamed (rows x kv_limit x steps x per-slot ring bytes
        # — the full-pool baseline pays pool x max_len every step)
        self.decode_rows = 0
        self.decode_kv_limit = 0
        self.kv_bytes_decode = 0
        # cumulative over launched decode steps: KV positions each step's
        # attention reads (rows x kv_limit), and of those the positions
        # live rows hold (pos + 1 per dispatched row)
        self.decode_kv_positions = 0
        self.decode_kv_positions_live = 0
        # cumulative over launched decode steps: KV bytes the steps' in-place
        # scatters write (active rows x one position's K/V of every layer)
        self.decode_kv_bytes_written = 0
        # queue wait: wall seconds from a flow's submit to the launch of
        # its first prefill chunk, and how many flows began prefill, by
        # priority; _waiting holds the submit instants of flows not begun
        self._waiting: Dict[int, float] = {}
        self.queue_wait_s = {Priority.REACTIVE: 0.0, Priority.PROACTIVE: 0.0}
        self.queue_waits = {Priority.REACTIVE: 0, Priority.PROACTIVE: 0}
        # shared-prefix KV reuse (DESIGN.md §10): a host-side radix index
        # over prompt token IDs; a hit replaces the matched prefix's forward
        # passes with ONE bounded row-to-row KV copy.  Only exact for
        # never-wrapping pure-attention rings, and it leans on in-pool
        # prefill (the copy IS an in-pool row write), so unsupported
        # configs and legacy modes silently fall back to cold prefill.
        from repro.core.prefixcache import DEFAULT_CAPACITY_TOKENS
        self._prefix: Optional[PrefixCache] = None
        if prefix_cache and self.in_pool_prefill and self.device_resident \
                and prefix_reuse_supported(cfg, max_len):
            self._prefix = PrefixCache(
                prefix_cache_tokens or DEFAULT_CAPACITY_TOKENS,
                block=prefix_block)
        self._hit: Dict[int, int] = {}  # req id -> matched prefix length
        self._hit_node: Dict[int, object] = {}  # req id -> pinned radix node
        # physical prefix sources: nodes backed by a live/free pool row
        # (slot -> node set), and the refcounted off-pool snapshot store for
        # prefixes whose donor slot was rebound (entry id -> entry)
        self._slot_nodes: Dict[int, set] = {}
        self._store: Dict[int, dict] = {}
        self._store_next = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self.prefix_copy_device_calls = 0  # row/store -> row prefix copies
        self.prefix_promotions = 0  # donor-slot rebinds snapshotted to store
        self.prefix_fallbacks = 0  # hits served by forward passes (no source)
        self.kv_bytes_prefix_copied = 0  # KV bytes moved by prefix copies
        self.prefill_forward_tokens = 0  # tokens that ran a real forward
        # memory-contention observability (paper §6.4, DESIGN.md §14): the
        # estimator tracks which stages are in flight RIGHT NOW (decode
        # segments register around their launch, prefills from first chunk
        # to prefill_done), and decode segments bucket their wall time by
        # whether a prefill overlapped — the measured overlapped-vs-solo
        # slowdown that calibrates the scheduler's CoExecutionCalibration.
        # bw_util constants mirror the HEG annotation regime: prefill is
        # compute-bound GEMM-like, decode memory-bound GEMV-like.
        self.prefill_bw_util = 0.35
        self.decode_bw_util = 0.85
        self._pressure_est = MemoryPressureEstimator()
        self._prefill_live: set = set()  # rids with an in-flight prefill
        self.contention_pressure_peak = 0.0
        self.co_executed_segments = 0  # decode segments with a live prefill
        self._seg_solo_time = 0.0  # decode-segment wall s, no prefill live
        self._seg_solo_steps = 0
        self._seg_co_time = 0.0  # decode-segment wall s, prefill(s) live
        self._seg_co_steps = 0

    # -- jitted callable cache (compilation count is O(log max_len)) --------
    def _jitted(self, key: tuple, build, donate=()):
        fn = self._jit_cache.get(key)
        if fn is None:
            if not self.device_resident:
                donate = ()  # legacy mode: every call copies its pool
            body = build()
            # the program is named after its kind: jit_decode_run,
            # jit_prefill_chunk, ... in the profiler's XLA Modules line
            body.__name__ = body.__qualname__ = key[0]
            fn = self._jax.jit(body, donate_argnums=donate)
            self._jit_cache[key] = fn
            self._programs[fn] = (key, _launch_meta(key))
            self.jit_compilations += 1
        return fn

    def _call(self, fn, *args, rid: Optional[int] = None,
              stage: str = "device"):
        """Launch one jitted program through the fault seam (DESIGN.md §12).

        The injector is consulted BEFORE the launch, so a failed dispatch
        never half-mutates the donated pool: retrying is a clean re-launch
        of the same program — which is exactly the abortable-segment replay
        of DESIGN.md §8 when the program is a decode segment.  Transient
        faults are retried up to ``device_fault_retries_max`` times, then
        escalate to ``PermanentDeviceFault``; flow-attributable call sites
        pass ``rid`` so a targeted fault quarantines only that flow.

        The whole call is the span ``backend.launch``; a program's first
        launch (trace, compile or compile-cache load) nests the span
        ``backend.compile``, which carries the program's jit key."""
        key, meta = self._programs.get(fn, (None, {}))
        if rid is not None:
            meta = {**meta, "rid": rid}
        with self.phases("backend.launch", "host_launch_s", **meta):
            if self._faults is not None:
                for _ in range(self._fault_retry_max + 1):
                    try:
                        self._faults.check("device", req_id=rid, stage=stage)
                        break
                    except TransientDeviceFault:
                        self.device_fault_retries += 1
                else:
                    raise PermanentDeviceFault(
                        f"transient device fault at {stage} persisted past "
                        f"{self._fault_retry_max} segment replays")
            if fn in self._launched:
                return fn(*args)
            self._launched.add(fn)
            with self.phases("backend.compile", "host_compile_s",
                             key=repr(key)):
                return fn(*args)

    def _extend_fn(self, c: int):
        from repro.models import extend
        cfg = self.cfg
        kb = self.kernel_backend

        def build():
            def fn(params, cache, toks):
                logits, cache = extend(cfg, params, cache, toks,
                                       kernel_backend=kb)
                return logits.argmax(-1).astype(self._jnp.int32)[0], cache
            return fn
        return self._jitted(("extend", c), build, donate=(1,))

    def _decode_fn(self, pool_size: int, rows: Optional[int] = None,
                   kv_limit: Optional[int] = None):
        """One masked decode iteration, elastic in both axes (DESIGN.md §9):
        the program computes only the leading ``rows`` pool rows (every live
        slot sits below the pow-2 row bound because the free list prefers
        low slots) and reads a ``kv_limit``-bounded window of each ring,
        while each layer writes its new K/V in place into the donated pool
        (``decode_program``).  ``rows == pool`` and ``kv_limit == max_len``
        are the full-pool program (the ``elastic_decode=False`` / ring-wrap
        fallback path)."""
        rows = pool_size if rows is None else rows
        kvl = self.max_len if kv_limit is None else kv_limit
        return self._jitted(
            ("decode", pool_size, rows, kvl),
            lambda: decode_program(self.cfg, self.kernel_backend,
                                   self.max_len, pool_size, rows, kvl),
            donate=(1, 2))

    def _decode_run_fn(self, pool_size: int, n_steps: int,
                       rows: Optional[int] = None,
                       kv_limit: Optional[int] = None):
        """``n_steps`` fused iterations with the same two-axis elasticity as
        :meth:`_decode_fn`; the caller's ``kv_limit`` covers the run's END
        (``next_pow2(max live pos + n_steps)``) so every position written
        mid-scan stays inside the bounded window."""
        rows = pool_size if rows is None else rows
        kvl = self.max_len if kv_limit is None else kv_limit
        return self._jitted(
            ("decode_run", pool_size, n_steps, rows, kvl),
            lambda: decode_program(self.cfg, self.kernel_backend,
                                   self.max_len, pool_size, rows, kvl,
                                   n_steps),
            donate=(1, 2))

    def _bind_fn(self, pool_size: int):
        from repro.models import write_slot

        def build():
            def fn(pool, one, slot, toks, first):
                return write_slot(pool, one, slot), toks.at[slot].set(first)
            return fn
        # the B=1 scratch (arg 1) is NOT donated: its buffers can never be
        # reused for the B=pool outputs, so donating it only emits warnings
        return self._jitted(("bind", pool_size), build, donate=(0, 3))

    def _prefill_chunk_fn(self, pool_size: int, sizes: tuple, tok_len: int,
                          *, kv_limit: int, fresh: bool, emit: bool):
        """In-pool prefill of (up to two) pow-2 sub-chunks as ONE jitted
        program over the donated pool, slicing tokens on device from the
        request's resident (1, tok_len) buffer.  No per-chunk host upload,
        no host sync; steady-state HEG chunks are a single pow-2 bucket, so
        a prompt chunk costs one or two device calls total.  ``sizes`` is
        capped at two buckets so the jit-key space stays the bounded
        O(log^2) of PR 1's shape bucketing — never one program per distinct
        chunk length.  Host-known row progress makes the statics cheap:

          kv_limit  static pow-2 bound on the row's live prefix after this
                    call: attention scores O(live prefix) keys, not
                    O(max_len) — early prompt chunks do a fraction of a
                    full-ring extend's attention work (the position-
                    oblivious scratch baseline always pays the full ring)
          fresh     first chunk of a (re)bound row — invalidate the
                    previous occupant first (``kvcache.reset_row``:
                    slot_pos flip + small state zeroing, NOT a KV rewrite)
          emit      last chunk — also commit the first output token to the
                    device-resident per-slot token vector (replaces the old
                    bind-time scatter; the host fetches it once at
                    prefill_done)
        """
        from repro.models import (extend, extend_row, read_row, reset_row,
                                  truncate_rings, write_row_slice)
        cfg = self.cfg
        jax, jnp = self._jax, self._jnp
        max_len = self.max_len
        kb = self.kernel_backend

        def build():
            def fn(params, pool, toks_vec, tok_buf, start, slot):
                if fresh:
                    pool = reset_row(pool, slot)
                if len(sizes) == 1:
                    chunk = jax.lax.dynamic_slice(
                        tok_buf, (jnp.int32(0), start), (1, sizes[0]))
                    logits, pool = extend_row(cfg, params, pool, chunk, slot,
                                              kv_limit=kv_limit,
                                              full_alloc=max_len,
                                              kernel_backend=kb)
                else:
                    # bucket pair: gather/truncate the row view once, extend
                    # per bucket, write the whole span back once
                    view = truncate_rings(read_row(pool, slot), kv_limit,
                                          max_len)
                    off = 0
                    for c in sizes:
                        chunk = jax.lax.dynamic_slice(
                            tok_buf, (jnp.int32(0), start + off), (1, c))
                        logits, view = extend(cfg, params, view, chunk,
                                              kernel_backend=kb)
                        off += c
                    pool = write_row_slice(pool, view, slot, start, off)
                nxt = logits.argmax(-1).astype(jnp.int32)[0]
                if emit:
                    toks_vec = toks_vec.at[slot].set(nxt)
                return nxt, toks_vec, pool
            return fn
        return self._jitted(("prefill_chunk", pool_size, sizes, tok_len,
                             kv_limit, fresh, emit), build, donate=(1, 2))

    def _prefix_copy_fn(self, pool_size: int, hit_cap: int):
        """Row-to-row shared-prefix copy (DESIGN.md §10): donor row ->
        freshly reset consumer row, bounded to the pow-2 ``hit_cap`` bucket
        with the traced ``hit`` masking the overhang.  Jit keys are
        ``(pool, hit_cap)`` — O(log max_len) programs, never one per hit."""
        from repro.models import copy_prefix_rows
        max_len = self.max_len

        def build():
            def fn(pool, src, dst, hit):
                return copy_prefix_rows(pool, src, dst, hit, hit_cap,
                                        max_len)
            return fn
        return self._jitted(("prefix_copy", pool_size, hit_cap), build,
                            donate=(0,))

    def _prefix_paste_fn(self, pool_size: int, entry_cap: int, hit_cap: int):
        """Store-entry -> consumer-row twin of :meth:`_prefix_copy_fn` (the
        entry is NOT donated: it is shared by every future consumer)."""
        from repro.models import paste_prefix
        max_len = self.max_len

        def build():
            def fn(pool, entry, dst, hit):
                return paste_prefix(pool, entry, dst, hit, hit_cap,
                                    entry_cap, max_len)
            return fn
        return self._jitted(("prefix_paste", pool_size, entry_cap, hit_cap),
                            build, donate=(0,))

    def _prefix_snap_fn(self, pool_size: int, depth_cap: int):
        """Donor-row snapshot at slot-rebind time.  The pool is NOT donated
        (it must survive — the snapshot is a read), so this is the one
        prefix program that pays a bounded O(depth_cap) copy by design."""
        from repro.models import snapshot_prefix
        max_len = self.max_len

        def build():
            def fn(pool, src):
                return snapshot_prefix(pool, src, depth_cap, max_len)
            return fn
        return self._jitted(("prefix_snap", pool_size, depth_cap), build)

    def _clear_fn(self, pool_size: int):
        def build():
            def fn(toks, mask, slot):
                return toks.at[slot].set(0), mask.at[slot].set(False)
            return fn
        return self._jitted(("clear", pool_size), build, donate=(0, 1))

    def _mask_update_fn(self, pool_size: int, k: int):
        def build():
            def fn(mask, idx, val):
                return mask.at[idx].set(val, mode="drop")
            return fn
        return self._jitted(("mask", pool_size, k), build, donate=(0,))

    # -- slot management -----------------------------------------------------
    def _grow_pool(self):
        """Double the pool — up to the hard ``pool_slots_max`` KV budget
        (DESIGN.md §12).  At the cap, growth is a typed ``AllocationFault``
        (quarantining only the requesting flow), never silent allocation:
        bounded-resource serving means the budget holds under any load."""
        from repro.models import copy_into_prefix, init_cache
        jnp, np = self._jnp, self._np
        old, p = self._pool, self.pool_slots
        target = p * 2 if self.pool_slots_max is None \
            else min(p * 2, self.pool_slots_max)
        if target <= p:
            raise AllocationFault(
                f"KV pool exhausted at pool_slots_max={self.pool_slots_max} "
                f"({p} slots bound, 0 free) and may not grow")
        self.pool_slots = target
        grown = target - p
        new = init_cache(self.cfg, self.params, self.pool_slots, self.max_len,
                         self.dtype, kv_dtype=self._kv_dtype_arg)
        # un-jitted on purpose: builds fresh (donation-safe) buffers
        self._pool = copy_into_prefix(new, old, p)
        for s in range(p, self.pool_slots):
            heapq.heappush(self._free, s)
        self._toks = jnp.concatenate(
            [self._toks, jnp.zeros((grown,), jnp.int32)])
        self._mask = jnp.concatenate([self._mask, jnp.zeros((grown,), bool)])
        self._mask_host = np.concatenate(
            [self._mask_host, np.zeros((grown,), bool)])

    def _alloc_slot(self, rid: int) -> int:
        """Bind the LOWEST free slot (min-heap): live rows stay compacted at
        the front of the pool, so the elastic row bound
        (``next_pow2(high_water + 1)``, DESIGN.md §9) tracks occupancy
        instead of allocation history.  If the popped row still backs radix
        prefixes, they are promoted to the store FIRST — the row's buffers
        are about to be reused (DESIGN.md §10).  Raises ``AllocationFault``
        (injected, or real at ``pool_slots_max``) instead of ever binding a
        row it does not have."""
        if self._faults is not None:
            self._faults.check("alloc", req_id=rid)
        if not self._free:
            self._grow_pool()
        slot = heapq.heappop(self._free)
        self._promote_donor(slot)
        self._slot[rid] = slot
        return slot

    # -- shared-prefix sources (DESIGN.md §10) --------------------------------
    def _set_source(self, node, src) -> None:
        """Re-point a radix node's physical KV source, keeping the reverse
        maps (slot -> nodes, store refcounts) consistent.  A store entry
        whose last referencing node departs is freed — its device buffers
        have no other owner."""
        old = node.source
        if old == src:
            return
        if old is not None:
            kind, ref = old
            if kind == "slot":
                nodes = self._slot_nodes.get(ref)
                if nodes is not None:
                    nodes.discard(node)
                    if not nodes:
                        del self._slot_nodes[ref]
            else:
                entry = self._store.get(ref)
                if entry is not None:
                    entry["refs"] -= 1
                    if entry["refs"] <= 0:
                        del self._store[ref]
        node.source = src
        if src is not None:
            kind, ref = src
            if kind == "slot":
                self._slot_nodes.setdefault(ref, set()).add(node)
            else:
                self._store[ref]["refs"] += 1

    def _promote_donor(self, slot: int) -> None:
        """A free slot that still backs indexed prefixes is being rebound:
        snapshot the deepest donated prefix into a refcounted store entry
        (ONE bounded device gather) and re-point every backed node at it.
        Promotion never drops an indexed prefix — the index stays a pure
        function of the insert/evict sequence, which is what keeps sim and
        real traces equal (the sim side has no promotions at all)."""
        nodes = self._slot_nodes.get(slot)
        if not nodes:
            return
        depth_cap = _next_pow2(max(n.depth for n in nodes))
        fn = self._prefix_snap_fn(self.pool_slots, depth_cap)
        entry_cache = self._call(fn, self._pool, self._jnp.int32(slot),
                                 stage="prefix_copy")
        eid = self._store_next
        self._store_next += 1
        self._store[eid] = {"cache": entry_cache, "cap": depth_cap,
                            "refs": 0}
        self.prefix_promotions += 1
        for n in list(nodes):
            self._set_source(n, ("store", eid))

    def _sync_mask(self, slots: List[int]):
        """Push the iteration's membership to the device mask as a (usually
        empty) scatter of changed entries, pow-2 padded with out-of-range
        indices so the update compiles O(log pool) programs total."""
        np = self._np
        want = np.zeros((self.pool_slots,), bool)
        want[slots] = True
        diff = np.nonzero(want != self._mask_host)[0]
        if len(diff) == 0:
            return
        k = _next_pow2(len(diff))
        idx = np.full((k,), self.pool_slots, np.int32)  # pad: dropped
        val = np.zeros((k,), bool)
        idx[:len(diff)] = diff
        val[:len(diff)] = want[diff]
        fn = self._mask_update_fn(self.pool_slots, k)
        self._mask = self._call(fn, self._mask, self._jnp.asarray(idx),
                                self._jnp.asarray(val), stage="mask")
        self._mask_host = want

    # -- prefill --------------------------------------------------------------
    def _ensure_scratch_at(self, req: Request, seq_start: int):
        """Scratch cache positioned at ``seq_start`` — rebuilt (replaying the
        already-prefetched prefix) after a discard-style preemption reset the
        scheduler's chunk progress."""
        from repro.models import init_cache
        rid = req.id
        if rid in self._scratch and self._scratch_pos[rid] == seq_start:
            return
        self._scratch[rid] = init_cache(self.cfg, self.params, 1,
                                        self.max_len, self.dtype,
                                        kv_dtype=self._kv_dtype_arg)
        self._scratch_pos[rid] = 0
        if seq_start > 0:
            self._run_bucketed(req, 0, seq_start)

    def _run_bucketed(self, req: Request, start: int, n: int):
        if n <= 0:  # zero-length chunk: nothing ran, ``nxt`` never exists
            return
        rid = req.id
        pos = start
        for size in _pow2_buckets(n):
            chunk = self._np.asarray(req.tokens[:, pos:pos + size],
                                     self._np.int32)
            fn = self._extend_fn(size)
            nxt, self._scratch[rid] = self._call(
                fn, self.params, self._scratch[rid],
                self._jnp.asarray(chunk), rid=rid, stage="prefill")
            self.prefill_device_calls += 1
            pos += size
        self._scratch_pos[rid] = pos
        self.kv_bytes_prefill += n * self._kv_token_bytes
        self.prefill_forward_tokens += n
        if pos >= req.prompt_len:  # last chunk -> first output token
            with self.phases("backend.fetch", "host_fetch_s", rid=rid):
                self._first[rid] = int(nxt)
            self.host_syncs += 1
            self.prefill_host_syncs += 1

    # -- in-pool prefill (DESIGN.md §7) ---------------------------------------
    def _upload_prompt(self, req: Request):
        """Device-resident prompt tokens: uploaded ONCE per request, padded
        to the next power of two (O(log) distinct shapes), sliced on device
        per sub-chunk — no per-chunk host round trip."""
        rid = req.id
        buf = self._tok_dev.get(rid)
        if buf is None:
            np = self._np
            toks = np.asarray(req.tokens, np.int32).reshape(1, -1)
            pad = np.zeros((1, _next_pow2(max(toks.shape[1], 1))), np.int32)
            pad[:, :toks.shape[1]] = toks
            buf = self._tok_dev[rid] = self._jnp.asarray(pad)
        return buf

    def prefix_hit(self, req: Request) -> int:
        """Scheduler hook (arrival time): longest indexed prefix of the
        prompt, matched on exact token IDs and pinned until the request
        retires.  The matched prefix is served by ONE KV copy at the first
        prefill chunk (``_copy_prefix``); prefill kernels/ETC cover only
        the tail from ``seq_start = hit``.  Capped at ``prompt_len - 1``:
        at least one forward must run to produce the first output token."""
        if self._prefix is None or req.tokens is None:
            return 0
        self.prefix_prompt_tokens += req.prompt_len
        hit, node = self._prefix.match(_prompt_key(req),
                                       max_hit=req.prompt_len - 1)
        if hit <= 0 or node is None:
            return 0
        old = self._hit_node.pop(req.id, None)
        if old is not None:  # re-arrival of the same id: drop the stale pin
            self._prefix.unpin(old)
        self._prefix.pin(node)
        self._hit[req.id] = hit
        self._hit_node[req.id] = node
        self.prefix_hits += 1
        self.prefix_hit_tokens += hit
        return hit

    def _copy_prefix(self, req: Request, hit: int) -> int:
        """Serve a matched prefix into the request's freshly-bound row as
        one bounded KV copy; resolves the pinned node's physical source AT
        COPY TIME (the donor may have been promoted slot -> store since the
        match).  Returns the row position reached — 0 means no resolvable
        source (defensive; the caller falls back to forward passes, so a
        hit can slow down but never change tokens)."""
        node = self._hit_node.get(req.id)
        src = getattr(node, "source", None)
        if src is None:
            self.prefix_fallbacks += 1
            return 0
        jnp = self._jnp
        dst = self._slot[req.id]
        hit_cap = _next_pow2(hit)
        kind, ref = src
        if kind == "slot":
            if ref == dst:  # can't happen (promotion precedes rebinding)
                self.prefix_fallbacks += 1
                return 0
            fn = self._prefix_copy_fn(self.pool_slots, hit_cap)
            self._pool = self._call(fn, self._pool, jnp.int32(ref),
                                    jnp.int32(dst), jnp.int32(hit),
                                    rid=req.id, stage="prefix_copy")
        else:
            entry = self._store.get(ref)
            if entry is None:
                self.prefix_fallbacks += 1
                return 0
            fn = self._prefix_paste_fn(self.pool_slots, entry["cap"],
                                       min(hit_cap, entry["cap"]))
            self._pool = self._call(fn, self._pool, entry["cache"],
                                    jnp.int32(dst), jnp.int32(hit),
                                    rid=req.id, stage="prefix_copy")
        self.prefix_copy_device_calls += 1
        self.kv_bytes_prefix_copied += hit_cap * self._kv_token_bytes
        self._row_pos[req.id] = hit
        return hit

    def _ensure_row_at(self, req: Request, seq_start: int):
        """Pool row positioned at ``seq_start``: the slot is allocated at
        prefill START and its reused row invalidated by the next chunk's
        ``fresh`` program; a matched prefix is copied in (never forwarded)
        before any tail runs; a discard-style preemption that reset the
        scheduler's chunk progress re-invalidates the row and replays the
        already-prefetched prefix — re-copying the prefix too (the pinned
        node guarantees the source still exists)."""
        rid = req.id
        if rid in self._slot and self._row_pos.get(rid) == seq_start:
            return
        if rid not in self._slot:
            self._alloc_slot(rid)
        self._row_pos[rid] = None  # sentinel: next bucket resets the row
        self._nxt_dev.pop(rid, None)
        done = 0
        hit = min(self._hit.get(rid, 0), seq_start)
        if hit > 0:
            done = self._copy_prefix(req, hit)
        if seq_start > done:
            self._run_bucketed_in_pool(req, done, seq_start - done)

    def _run_bucketed_in_pool(self, req: Request, start: int, n: int):
        if n <= 0:  # zero-length chunk: nothing ran, nothing to dispatch
            return
        rid = req.id
        jnp = self._jnp
        buf = self._upload_prompt(req)
        buckets = _pow2_buckets(n)
        # group buckets in pairs: one device call per group, jit-key space
        # stays bounded (see _prefill_chunk_fn)
        groups = [tuple(buckets[i:i + 2]) for i in range(0, len(buckets), 2)]
        fresh = self._row_pos.get(rid) is None
        pos = start
        for sizes in groups:
            gstart, pos = pos, pos + sum(sizes)
            fn = self._prefill_chunk_fn(self.pool_slots, sizes, buf.shape[1],
                                        kv_limit=_next_pow2(pos),
                                        fresh=fresh,
                                        emit=pos >= req.prompt_len)
            nxt, self._toks, self._pool = self._call(
                fn, self.params, self._pool, self._toks, buf,
                jnp.int32(gstart), jnp.int32(self._slot[rid]),
                rid=rid, stage="prefill")
            self.prefill_device_calls += 1
            fresh = False
        self._row_pos[rid] = pos
        self.kv_bytes_prefill += n * self._kv_token_bytes
        self.prefill_forward_tokens += n
        if pos >= req.prompt_len:
            # keep the first output token on device: ONE host sync per
            # request happens at prefill_done, not per chunk
            self._nxt_dev[rid] = nxt

    def register(self, req: Request,
                 on_token: Optional[TokenCallback] = None) -> None:
        if self.cfg.is_encoder_decoder:
            # guarded again here (not just in __init__) so a subclass or a
            # future constructor relaxation can never reach the slot pool
            # with cross-attention state reset_row won't invalidate
            raise NotImplementedError(self._ENC_DEC_MSG)
        if on_token is not None:
            self._on_token[req.id] = on_token
        if req.submit_wall is not None:
            self._waiting[req.id] = req.submit_wall
        if self.in_pool_prefill and req.tokens is not None:
            self._upload_prompt(req)

    def _begin_prefill(self, req: Request) -> None:
        """A flow's first prefill chunk launches: count its queue wait."""
        t = self._waiting.pop(req.id, None)
        if t is not None:
            self.queue_wait_s[req.priority] += time.perf_counter() - t
            self.queue_waits[req.priority] += 1

    # -- per-flow fault isolation (DESIGN.md §12) -----------------------------
    def _record_flow_fault(self, req: Request, exc: BaseException,
                           stage: str) -> None:
        """Park a flow-attributable failure for the scheduler's per-turn
        poll: the flow is marked quarantined (its remaining hooks no-op)
        and every OTHER flow's state — including buffered fused-run replay
        rows — is untouched.  ``isolate_flow_faults=False`` restores the
        pre-PR-8 raise-out teardown."""
        self.flow_faults += 1
        if not self.isolate_flow_faults:
            raise exc
        self._quarantined.add(req.id)
        self._pending_faults.append(FlowFault(req, exc, stage))

    def take_flow_faults(self) -> List[FlowFault]:
        out, self._pending_faults = self._pending_faults, []
        return out

    def deadline_expired(self, req: Request, now: float) -> bool:
        if self._faults is not None and \
                self._faults.fires("deadline", req_id=req.id):
            return True
        return super().deadline_expired(req, now)

    def _track_prefill(self, rid: int) -> None:
        """Register an in-flight prefill with the pressure estimator (first
        chunk only); removed at ``prefill_done`` / flow teardown."""
        if rid not in self._prefill_live:
            self._prefill_live.add(rid)
            self._pressure_est.add(f"prefill:{rid}", self.prefill_bw_util)
            self.contention_pressure_peak = max(
                self.contention_pressure_peak, self._pressure_est.pressure)

    def _untrack_prefill(self, rid: int) -> None:
        if rid in self._prefill_live:
            self._prefill_live.discard(rid)
            self._pressure_est.remove(f"prefill:{rid}")

    def prefill_chunk(self, req: Request, seq_start: int, tokens: int,
                      now: float) -> None:
        if req.tokens is None or req.id in self._quarantined:
            return
        self._begin_prefill(req)
        self._track_prefill(req.id)
        try:
            if self.in_pool_prefill:
                self._ensure_row_at(req, seq_start)
                self._run_bucketed_in_pool(req, seq_start, tokens)
            else:
                self._ensure_scratch_at(req, seq_start)
                self._run_bucketed(req, seq_start, tokens)
        except FaultError as e:
            self._record_flow_fault(req, e, "prefill")

    def prefill_done(self, req: Request, now: float) -> None:
        if req.id in self._quarantined:
            return
        self._untrack_prefill(req.id)
        try:
            self._prefill_done(req, now)
        except FaultError as e:
            self._record_flow_fault(req, e, "prefill")

    def _prefill_done(self, req: Request, now: float) -> None:
        rid = req.id
        if self.in_pool_prefill:
            if req.tokens is None or rid not in self._slot:
                return
            nxt = self._nxt_dev.pop(rid, None)
            if nxt is None:
                # prefill made entirely of zero-length chunks: no program
                # ran (so the row still holds its PREVIOUS occupant's state
                # — every rebind must run, and runs, the ``fresh`` reset)
                # and there is no token to decode on; return the never
                # masked-in slot to the free list
                heapq.heappush(self._free, self._slot.pop(rid))
                self._row_pos.pop(rid, None)
                return
            # the last chunk's ``emit`` program already committed the first
            # token to the device token vector; fetch it once for streaming
            with self.phases("backend.fetch", "host_fetch_s", rid=rid):
                first = int(nxt)
            self.host_syncs += 1
            self.prefill_host_syncs += 1
            self._row_pos.pop(rid, None)
        else:
            # the _first guard covers a prefill made entirely of zero-length
            # chunks: no forward ran, so there is no token to bind a slot on
            if req.tokens is None or rid not in self._scratch \
                    or rid not in self._first:
                return
            slot = self._alloc_slot(rid)
            fn = self._bind_fn(self.pool_slots)
            first = self._first.pop(rid)
            self._pool, self._toks = self._call(
                fn, self._pool, self._scratch.pop(rid),
                self._jnp.int32(slot), self._toks, self._jnp.int32(first),
                rid=rid, stage="prefill")
            self._scratch_pos.pop(rid, None)
            self.bind_device_calls += 1
            self.kv_bytes_prefill += self._bind_row_bytes
        # host-known row progress: decode dispatches derive their static
        # pow-2 kv_limit from the max live position of the batch (§9)
        self._slot_pos[self._slot[rid]] = req.prompt_len
        # index the finished prompt as a donor (DESIGN.md §10) — but only
        # when the row can NEVER ring-wrap (wrap would overwrite the donated
        # prefix).  The gate is static per request, so sim models it too.
        if self._prefix is not None and req.tokens is not None \
                and rid in self._slot \
                and req.prompt_len + req.max_new_tokens <= self.max_len:
            path, evicted = self._prefix.insert(_prompt_key(req))
            slot = self._slot[rid]
            for n in path:
                self._set_source(n, ("slot", slot))
            for n in evicted:
                self._set_source(n, None)
        self._commit_first(req, first)

    # -- decode ---------------------------------------------------------------
    def decode_run(self, reqs: List[Request], n_steps: int,
                   now: float) -> None:
        """Commit to a membership-stable run.  Abortable mode (default)
        launches only the first ``decode_segment_steps``-iteration segment
        now and the rest lazily as the replay buffer drains, so a reactive
        arrival between segments cancels the unlaunched remainder
        (``request_preempt``) at a kernel boundary.  ``abortable_runs=False``
        executes the whole plan eagerly (one blocking launch chain, one host
        sync) — PR 2's behaviour, kept as the BENCH_reactive baseline."""
        live = [r for r in reqs if r.id in self._slot
                and r.id not in self._quarantined]
        if not live or n_steps <= 1 or not self.device_resident:
            return
        slots = [self._slot[r.id] for r in live]
        self._sync_mask(slots)
        self._fused_rows = deque()
        self._fused_slots = frozenset(slots)
        self._fused_left = int(n_steps)
        self.fused_runs += 1
        self._run_segment()

    # -- elastic dispatch extents (DESIGN.md §9) ------------------------------
    def _elastic_extent(self, slots: List[int], n: int) -> tuple:
        """Static ``(rows, kv_limit)`` jit-key pair for a decode dispatch of
        ``n`` iterations over pool ``slots``:

          rows      ``next_pow2(high_water_live_slot + 1)`` — every dispatched
                    slot sits below it (low-slot allocation keeps it tight);
                    bound slots at or beyond it are simply not computed, and
                    bound-but-inactive slots below it are computed-and-masked
                    exactly as in the full-pool program.
          kv_limit  ``next_pow2(max live row position + n)`` — covers every
                    ring slot the run can read or write, since a non-wrapped
                    row's ring slot index equals its position.  A row that
                    wrapped (pos >= max_len) or whose progress is unknown
                    pushes the bound to ``max_len``, turning the truncation
                    into the identity — the exactness-first fallback.
                    Window-shrunk ring leaves (alloc < max_len) are never
                    truncated at all (`kvcache.truncate_rings`).
        """
        if not self.elastic_decode:
            return self.pool_slots, self.max_len
        rows = min(_next_pow2(max(slots) + 1), self.pool_slots)
        pos = [self._slot_pos.get(s) for s in slots]
        if any(p is None for p in pos):
            return rows, self.max_len
        return rows, min(_next_pow2(max(pos) + n), self.max_len)

    def _account_decode(self, slots: List[int], n: int, rows: int, kvl: int):
        """Advance host-tracked row positions past an ``n``-step dispatch
        and fold its extent into the elastic counters: each of its steps
        reads ``rows x kvl`` KV positions, of which a live row at position
        ``p`` holds ``p + 1`` (all ``kvl`` once its ring has wrapped), and
        writes one position's K/V of every layer for each dispatched row."""
        live = 0
        for s in slots:
            p = self._slot_pos.get(s)
            if p is None:
                continue
            if p + n <= kvl:  # steps read p + 1 .. p + n positions
                live += n * (p + 1) + n * (n - 1) // 2
            else:
                live += sum(min(p + j + 1, kvl) for j in range(n))
            self._slot_pos[s] = p + n
        self.decode_rows, self.decode_kv_limit = rows, kvl
        self.kv_bytes_decode += n * rows * kvl * self._kv_token_bytes
        self.decode_kv_positions += n * rows * kvl
        self.decode_kv_positions_live += live
        self.decode_kv_bytes_written += n * len(slots) * self._kv_token_bytes

    def _run_segment(self) -> None:
        """Launch the next bounded ``lax.scan`` segment of the committed run
        and fetch its token block (ONE host sync per segment)."""
        n = min(self._fused_left, self.decode_segment_steps) \
            if self.abortable_runs else self._fused_left
        if n <= 0:
            return
        slots = sorted(self._fused_slots)
        # contention observability (§6.4): register the segment with the
        # pressure estimator and bucket its wall time (its launches and
        # the fetch of its token block, from the phase clocks) by whether
        # a prefill overlapped it
        co_executed = bool(self._prefill_live)
        self._pressure_est.add("decode", self.decode_bw_util)
        self.contention_pressure_peak = max(
            self.contention_pressure_peak, self._pressure_est.pressure)
        clocks_before = self.phases.total(_SEGMENT_CLOCKS)
        blocks = []
        for b in _pow2_buckets(n):
            rows, kvl = self._elastic_extent(slots, b)
            fn = self._decode_run_fn(self.pool_slots, b, rows, kvl)
            block, self._toks, self._pool = self._call(
                fn, self.params, self._pool, self._toks, self._mask,
                stage="decode")
            self.decode_device_calls += 1
            self._account_decode(slots, b, rows, kvl)
            blocks.append(block)
        with self.phases("backend.fetch", "host_fetch_s", rows=len(slots),
                         steps=n):
            full = self._np.asarray(self._jnp.concatenate(blocks, axis=0)
                                    if len(blocks) > 1 else blocks[0])
        seg_wall = self.phases.total(_SEGMENT_CLOCKS) - clocks_before
        self._pressure_est.remove("decode")
        if co_executed:
            self.co_executed_segments += 1
            self._seg_co_time += seg_wall
            self._seg_co_steps += n
        else:
            self._seg_solo_time += seg_wall
            self._seg_solo_steps += n
        self.host_syncs += 1
        self._fused_rows.extend(full)
        self._fused_left -= n
        self.fused_steps += n
        self.decode_segments += 1

    def request_preempt(self, now: float) -> None:
        """Cancel every decode segment of the committed run that has not
        launched yet.  Buffered (already-executed) rows stay: the scheduler
        replays them so the event-horizon commitment of the truncated plan
        still holds token-exactly."""
        if self._fused_left > 0:
            self.aborted_runs += 1
            self.aborted_steps += self._fused_left
            self._fused_left = 0
            if not self._fused_rows:
                self._fused_slots = None

    def decode_iteration(self, reqs: List[Request], now: float) -> None:
        live = [r for r in reqs if r.id in self._slot
                and r.id not in self._quarantined]
        if not live:
            return
        if self._fused_rows or (self._fused_slots is not None
                                and self._fused_left > 0):
            if not self._fused_rows:
                self._run_segment()  # lazy: next segment only when needed
            self._replay_row(live)
            return
        slots = [self._slot[r.id] for r in live]
        if self.device_resident:
            self._sync_mask(slots)
            toks, mask = self._toks, self._mask
        else:
            # legacy (pre-donation) hot path: rebuild the batch state on the
            # host and re-upload it every iteration
            np = self._np
            mask_h = np.zeros((self.pool_slots,), bool)
            toks_h = np.zeros((self.pool_slots,), np.int32)
            for r in live:
                s = self._slot[r.id]
                mask_h[s] = True
                toks_h[s] = self._last[r.id]
            toks, mask = self._jnp.asarray(toks_h), self._jnp.asarray(mask_h)
        rows, kvl = self._elastic_extent(slots, 1)
        fn = self._decode_fn(self.pool_slots, rows, kvl)
        nxt, self._toks, self._pool = self._call(
            fn, self.params, self._pool, toks, mask, stage="decode")
        self.decode_device_calls += 1
        self._account_decode(slots, 1, rows, kvl)
        with self.phases("backend.fetch", "host_fetch_s", rows=len(live)):
            nxt = self._np.asarray(nxt)
        self.host_syncs += 1
        self._commit(live, nxt)

    def _replay_row(self, live: List[Request]):
        """One committed iteration of an already-executed fused run: tokens
        come from the buffered block — no device call, no host sync."""
        slots = frozenset(self._slot[r.id] for r in live)
        if slots != self._fused_slots:
            raise RuntimeError(
                "decode batch membership diverged from the announced fused "
                f"run (planned slots {sorted(self._fused_slots)}, got "
                f"{sorted(slots)}) — the scheduler's event horizon must be "
                "a guaranteed lower bound")
        row = self._fused_rows.popleft()
        if not self._fused_rows and self._fused_left <= 0:
            self._fused_slots = None  # plan fully executed AND replayed
        self._commit(live, row)

    def _commit_first(self, req: Request, first: int):
        with self.phases("backend.commit", "host_commit_s", tokens=1):
            self._last[req.id] = first
            self._texts[req.id] = [first]
            self._emit(req, first)

    def _commit(self, live: List[Request], tokens_by_slot):
        with self.phases("backend.commit", "host_commit_s",
                         tokens=len(live)):
            for r in live:
                t = int(tokens_by_slot[self._slot[r.id]])
                self._last[r.id] = t
                self._texts[r.id].append(t)
                self._emit(r, t)

    def _drop_flow_state(self, rid: int) -> None:
        """Free one flow's slot and host bookkeeping — shared by ``finish``
        (normal retirement) and ``quarantine_flow`` (fault/deadline abort).
        ``_texts`` survives on purpose: ``output_tokens()`` outlives the
        run, so a failed flow's PARTIAL output stays retrievable."""
        slot = self._slot.pop(rid, None)
        if slot is not None:
            # clear the slot's last-token / mask state so a stale token can
            # never leak into a future bind's first masked step
            fn = self._clear_fn(self.pool_slots)
            try:
                self._toks, self._mask = self._call(
                    fn, self._toks, self._mask, self._jnp.int32(slot),
                    rid=rid, stage="finish")
            except FaultError:
                # an injected fault at the finish boundary fires BEFORE the
                # launch, so forcing the clear through is a clean re-launch:
                # slot reclamation must never leak on a cleanup fault
                self.flow_faults += 1
                self._toks, self._mask = fn(self._toks, self._mask,
                                            self._jnp.int32(slot))
            self._mask_host[slot] = False
            self._slot_pos.pop(slot, None)
            heapq.heappush(self._free, slot)
        self._untrack_prefill(rid)
        self._waiting.pop(rid, None)
        self._last.pop(rid, None)
        self._scratch.pop(rid, None)
        self._scratch_pos.pop(rid, None)
        self._first.pop(rid, None)
        self._on_token.pop(rid, None)
        self._tok_dev.pop(rid, None)
        self._row_pos.pop(rid, None)
        self._nxt_dev.pop(rid, None)
        # release the consumer's prefix pin; the request's OWN donated
        # prefix (if indexed at prefill_done) outlives it — the freed row
        # keeps its KV until rebinding promotes the prefix to the store
        self._hit.pop(rid, None)
        node = self._hit_node.pop(rid, None)
        if node is not None and self._prefix is not None:
            self._prefix.unpin(node)

    def finish(self, req: Request, now: float) -> None:
        slot = self._slot.get(req.id)
        if slot is not None and self._fused_slots is not None \
                and slot in self._fused_slots:
            # a planned member vanished mid-run (release cut-off): the
            # remaining buffered rows and unlaunched segments are stale
            self._fused_rows.clear()
            self._fused_slots = None
            self._fused_left = 0
        self._quarantined.discard(req.id)
        self._drop_flow_state(req.id)

    def quarantine_flow(self, req: Request, now: float) -> None:
        """Surgically retire ONE failed/expired flow (DESIGN.md §12).

        Unlike ``finish`` on a fused-plan member — which declares the whole
        replay buffer stale — quarantine cancels only the UNLAUNCHED
        segments (the abort boundary) and removes the dead flow's slot from
        the committed membership, keeping every survivor's buffered rows:
        their KV has already advanced through those iterations, so dropping
        the rows would desynchronize tokens from state.  The scheduler
        mirrors this truncation on its plan (``_quarantine``)."""
        rid = req.id
        self._quarantined.discard(rid)
        self._pending_faults = [f for f in self._pending_faults
                                if f.req_id != rid]
        slot = self._slot.get(rid)
        if slot is not None and self._fused_slots is not None \
                and slot in self._fused_slots:
            if self._fused_left > 0:
                # cancel unlaunched segments at the boundary (same
                # accounting as request_preempt)
                self.aborted_runs += 1
                self.aborted_steps += self._fused_left
                self._fused_left = 0
            rest = self._fused_slots - {slot}
            if rest and self._fused_rows:
                self._fused_slots = rest  # survivors replay token-exactly
            else:
                self._fused_rows.clear()
                self._fused_slots = None
        self._drop_flow_state(rid)
        self.quarantined_flows += 1

    def release(self, reqs: List[Request], now: float) -> None:
        """Free resources of requests cut off mid-flight (simulation hit
        max_time before they finished): their slot and scratch cache would
        otherwise stay bound across subsequent runs."""
        dropped = {r.id for r in reqs}
        self._pending_faults = [f for f in self._pending_faults
                                if f.req_id not in dropped]
        for r in reqs:
            self.finish(r, now)
        self._fused_rows.clear()  # uncommitted fused tokens are dropped
        self._fused_slots = None
        self._fused_left = 0

    # -- output ----------------------------------------------------------------
    def _emit(self, req: Request, token: int):
        """Per-token user-hook boundary.  With ``isolate_flow_faults`` (the
        default) an exception from ONE flow's callback — or an injected
        "hook" fault — is parked as a ``FlowFault`` for the scheduler's
        per-turn poll instead of unwinding the event loop: the flow is
        quarantined as ``failed`` while every other flow keeps streaming.
        ``isolate_flow_faults=False`` restores the raise-out teardown."""
        if req.id in self._quarantined:
            return  # flow already faulted: suppress further emissions
        cb = self._on_token.get(req.id)
        try:
            if self._faults is not None:
                self._faults.check("hook", req_id=req.id)
            if cb is not None:
                cb(req, token)
        except Exception as e:
            self._record_flow_fault(req, e, "hook")

    def output_tokens(self, req_id: int) -> list:
        return self._texts.get(req_id, [])

    # -- bounded-resource accounting (DESIGN.md §12) --------------------------
    def kv_store_rows(self) -> int:
        return len(self._store)

    def evict_prefix_leaves(self) -> int:
        """Degradation-ladder rung 1: under admission pressure the prefix
        cache is ballast — force-evict every unpinned node and drop its
        physical source.  Off-pool snapshot entries whose last node departs
        are freed (real KV rows back); donor-slot sources merely unlink
        (the pool row belongs to the free list / its flow regardless)."""
        if self._prefix is None:
            return 0
        before = len(self._store)
        nodes = self._prefix.evict_unpinned()
        for n in nodes:
            self._set_source(n, None)
        self.pressure_evicted_nodes += len(nodes)
        return before - len(self._store)

    def validate(self, strict: bool = False) -> List[str]:
        """Invariant catalogue (DESIGN.md §12): audits the accounting that
        every failure path must preserve.  O(pool + index) host work, no
        device sync — cheap enough to run after every event-loop turn
        under ``REPRO_STRICT_INVARIANTS=1``."""
        problems: List[str] = []
        free = list(self._free)
        bound = dict(self._slot)
        # 1. the free heap holds unique, in-range, unbound slots
        if len(set(free)) != len(free):
            problems.append(f"free heap has duplicates: {sorted(free)}")
        if any(s < 0 or s >= self.pool_slots for s in free):
            problems.append(f"free heap out of range: {sorted(free)}")
        overlap = set(free) & set(bound.values())
        if overlap:
            problems.append(f"slots both free and bound: {sorted(overlap)}")
        # 2. conservation: every pool slot is exactly free or bound
        if len(free) + len(bound) != self.pool_slots:
            problems.append(
                f"slot leak: {len(free)} free + {len(bound)} bound "
                f"!= {self.pool_slots} pool slots")
        # 3. per-slot live state only exists for bound slots
        stale_pos = set(self._slot_pos) - set(bound.values())
        if stale_pos:
            problems.append(f"_slot_pos for unbound slots: "
                            f"{sorted(stale_pos)}")
        stale_mask = [s for s in range(self.pool_slots)
                      if self._mask_host[s] and s not in bound.values()]
        if stale_mask:
            problems.append(f"mask set for unbound slots: {stale_mask}")
        # 4. committed fused membership covers only bound slots
        if self._fused_slots is not None:
            ghost = set(self._fused_slots) - set(bound.values())
            if ghost:
                problems.append(f"fused plan over unbound slots: "
                                f"{sorted(ghost)}")
        # 5. prefix accounting: node sources, store refcounts, pins
        if self._prefix is not None:
            refs: Dict[int, int] = {}
            stack = [self._prefix.root]
            while stack:
                nd = stack.pop()
                stack.extend(nd.children.values())
                src = nd.source
                if src is None:
                    continue
                kind, ref = src
                if kind == "slot":
                    if nd not in self._slot_nodes.get(ref, set()):
                        problems.append(
                            f"node {nd.nid} claims slot {ref} but is not "
                            f"in _slot_nodes")
                else:
                    if ref not in self._store:
                        problems.append(
                            f"node {nd.nid} references dropped store "
                            f"entry {ref}")
                    refs[ref] = refs.get(ref, 0) + 1
            for eid, entry in self._store.items():
                if entry["refs"] != refs.get(eid, 0):
                    problems.append(
                        f"store entry {eid} refcount {entry['refs']} != "
                        f"{refs.get(eid, 0)} referencing nodes")
                if entry["refs"] <= 0:
                    problems.append(f"store entry {eid} kept at refs<=0")
            # consumer pins: every pinned node's refs equals its pin count
            pins: Dict[int, int] = {}
            by_id: Dict[int, object] = {}
            for node in self._hit_node.values():
                pins[id(node)] = pins.get(id(node), 0) + 1
                by_id[id(node)] = node
            for key, n_pins in pins.items():
                node = by_id[key]
                if node.refs != n_pins:
                    problems.append(
                        f"node {node.nid} refs {node.refs} != {n_pins} "
                        f"in-flight consumer pins")
            if set(self._hit) != set(self._hit_node):
                problems.append(
                    f"hit/hit_node key mismatch: {sorted(self._hit)} vs "
                    f"{sorted(self._hit_node)}")
        if strict and problems:
            raise InvariantViolation("; ".join(problems))
        return problems

    def stats(self) -> dict:
        return {"jit_compilations": self.jit_compilations,
                "decode_device_calls": self.decode_device_calls,
                "prefill_device_calls": self.prefill_device_calls,
                "host_syncs": self.host_syncs,
                "fused_steps": self.fused_steps,
                "fused_runs": self.fused_runs,
                "decode_segments": self.decode_segments,
                "aborted_runs": self.aborted_runs,
                "aborted_steps": self.aborted_steps,
                "prefill_host_syncs": self.prefill_host_syncs,
                "bind_device_calls": self.bind_device_calls,
                "kv_bytes_prefill": self.kv_bytes_prefill,
                "decode_rows": self.decode_rows,
                "decode_kv_limit": self.decode_kv_limit,
                "kv_bytes_decode": self.kv_bytes_decode,
                "decode_kv_positions": self.decode_kv_positions,
                "decode_kv_positions_live": self.decode_kv_positions_live,
                "decode_kv_bytes_written": self.decode_kv_bytes_written,
                "queue_wait_s_reactive": self.queue_wait_s[Priority.REACTIVE],
                "queue_wait_s_proactive":
                    self.queue_wait_s[Priority.PROACTIVE],
                "queue_waits_reactive": self.queue_waits[Priority.REACTIVE],
                "queue_waits_proactive": self.queue_waits[Priority.PROACTIVE],
                **self.phases.stats(),
                "pool_slots": self.pool_slots,
                # bounded-resource failure model (DESIGN.md §12)
                "pool_slots_max": self.pool_slots_max,
                "free_slots": len(self._free),
                "device_fault_retries": self.device_fault_retries,
                "flow_faults": self.flow_faults,
                "quarantined_flows": self.quarantined_flows,
                "pressure_evicted_nodes": self.pressure_evicted_nodes,
                **(self._faults.stats() if self._faults is not None
                   else {}),
                "kv_dtype": self.kv_dtype,
                "kernel_backend": self.kernel_backend,
                "quant_scale_bytes": self.quant_scale_bytes,
                "prefix_hits": self.prefix_hits,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_hit_rate": self.prefix_hit_tokens
                / max(self.prefix_prompt_tokens, 1),
                "kv_bytes_prefix_copied": self.kv_bytes_prefix_copied,
                "prefix_copy_device_calls": self.prefix_copy_device_calls,
                "prefix_promotions": self.prefix_promotions,
                "prefix_fallbacks": self.prefix_fallbacks,
                "prefix_store_entries": len(self._store),
                "prefill_forward_tokens": self.prefill_forward_tokens,
                **self._contention_stats(),
                **(self._prefix.stats() if self._prefix is not None
                   else {})}

    def _contention_stats(self) -> dict:
        """Memory-contention observability (paper §6.4, DESIGN.md §14):
        live pressure, its high-water mark, how often decode co-executed
        with a prefill, the measured overlapped/solo decode slowdown (None
        until both buckets have samples), and the §6.4 model's prediction
        for the same stage pair."""
        solo = self._seg_solo_time / self._seg_solo_steps \
            if self._seg_solo_steps else None
        co = self._seg_co_time / self._seg_co_steps \
            if self._seg_co_steps else None
        measured = (co / solo) if (solo and co) else None
        model_rates = co_execution_rates(
            [self.prefill_bw_util, self.decode_bw_util])
        return {
            "prefill_bw_util": self.prefill_bw_util,
            "decode_bw_util": self.decode_bw_util,
            "contention_pressure": self._pressure_est.pressure,
            "contention_pressure_peak": self.contention_pressure_peak,
            "co_executed_segments": self.co_executed_segments,
            "co_execution_rate": self.co_executed_segments
            / max(self.decode_segments, 1),
            "co_execution_decode_slowdown_measured": measured,
            "co_execution_decode_slowdown_model":
                1.0 / max(model_rates[1], 1e-9),
            "co_execution_prefill_slowdown_model":
                1.0 / max(model_rates[0], 1e-9),
        }


class DualDeviceBackend(JaxRealBackend):
    """Stage-decoupled dual-backend execution (DESIGN.md §14): prefill runs
    on a second JAX device (the paper's NPU analogue) while decode — and
    the KV pool it owns — stays on device 0 (the iGPU analogue).

    A staged prefill forwards its prompt chunks through a B=1 staging
    cache resident on the prefill device, with the running next-token
    scalar kept ON DEVICE between chunks — no host sync anywhere in the
    prompt phase, so the prefill device's queue fills asynchronously while
    decode segments of live flows keep launching on (and syncing only
    with) the decode device.  At ``prefill_done`` — a scheduler turn, i.e.
    an abortable-segment boundary — the staged row is handed off: the ring
    prefix is truncated to the prompt's pow-2 bucket on the prefill device
    (bounding transfer bytes), ``device_put`` across, and installed into a
    freshly allocated pool row by ``kvcache.handoff_row`` (reset +
    ring-indexed scatter, the same primitives in-pool prefill and
    ``paste_prefix`` use).  ONE host sync per prefill (the first token)
    waits only on the prefill device's dependency chain.

    Without ``prefill_device`` the backend takes device 1 and raises
    ``MeshDeviceError`` when there is none; passing the decode device
    itself asks for co-location (``dual_device`` False: every flow runs
    the inherited in-pool path).

    Elastic operator binding (HEG): a prefill falls back to co-located
    execution on the decode device — the inherited in-pool path, byte-
    identical tokens — when the staging queue is at
    ``prefill_inflight_max`` (backpressure), the prompt has a prefix-cache
    hit (the matched KV lives in the decode pool; copying it to the
    staging device and back would cost more than the tail forward), or the
    two devices differ in kind and the HEG affinity tables price the
    prefill-lane ETC above the decode lane.
    The decision is sticky per request so a prefill never migrates devices
    mid-prompt.

    Everything here is backend-local: the scheduler drives the identical
    hook sequence either way, so sim==real trace equality extends to the
    dual-device path by construction.
    """

    name = "jax-dual"

    def __init__(self, cfg, params, *, prefill_device=None,
                 prefill_inflight_max: int = 8, heg=None, **kw):
        super().__init__(cfg, params, **kw)
        jax = self._jax
        self.heg = heg
        self.prefill_inflight_max = max(int(prefill_inflight_max), 1)
        self.decode_device = next(iter(self._pool["pos"].devices()))
        pf = prefill_device
        if pf is None:
            # a second device is required: co-location is asked for by
            # passing prefill_device=<the decode device>, never inferred
            from repro.launch.mesh import dual_stage_devices
            _, pf = dual_stage_devices()
        self.prefill_device = pf
        # staging leans on donation and the in-pool decode tail; the legacy
        # baselines fall back to co-located execution wholesale
        self.dual_device = (pf != self.decode_device
                            and self.in_pool_prefill
                            and self.device_resident)
        self._params_pf = jax.device_put(params, pf) \
            if self.dual_device else None
        # the HEG prices a heterogeneous SoC's NPU lane against its iGPU;
        # two devices of one kind run the same programs alike, so its
        # affinity tables cannot rank them
        self._price_lanes = (self.dual_device and pf.device_kind
                             != self.decode_device.device_kind)
        self._staged: set = set()  # rids prefilling on the prefill device
        self._stage_decision: Dict[int, bool] = {}  # sticky per request
        self._tok_dev_pf: Dict[int, object] = {}  # prompt uploads, pf device
        # recycled staging caches (bounded by prefill_inflight_max): a
        # fresh init_cache per prefill is the dominant fixed cost of
        # staging, and ``reset_row(cache, 0)`` restores a used one to the
        # fresh-bind state by the exact argument pool-row reuse rests on
        # (slot_pos=-1 masks stale payload, pos/recurrent zeroed)
        self._staging_free: List = []
        self.staged_prefills = 0
        self.prefill_inflight_peak = 0
        self.handoff_device_calls = 0  # pool installs of staged rows
        self.kv_bytes_handoff = 0  # ring bytes moved across the handoff
        self.colocated_hits = 0  # fallbacks: prefix hit on the decode pool
        self.colocated_backpressure = 0  # fallbacks: staging queue full
        self.colocated_affinity = 0  # fallbacks: HEG priced the lane out

    # -- staged prefill programs (prefill device) -----------------------------
    def _staged_extend_fn(self, c: int, tok_len: int):
        """One pow-2 prefill bucket against the B=1 staging cache, slicing
        tokens on device from the resident (1, tok_len) buffer and keeping
        the next-token scalar on device.  Placement follows the committed
        args (staging cache + ``_params_pf`` live on the prefill device),
        so the same jit entry serves either device with its own
        executable."""
        from repro.models import extend
        cfg = self.cfg
        jax, jnp = self._jax, self._jnp
        kb = self.kernel_backend

        def build():
            def fn(params, cache, tok_buf, start):
                chunk = jax.lax.dynamic_slice(
                    tok_buf, (jnp.int32(0), start), (1, c))
                logits, cache = extend(cfg, params, cache, chunk,
                                       kernel_backend=kb)
                return logits.argmax(-1).astype(jnp.int32)[0], cache
            return fn
        return self._jitted(("staged_extend", c, tok_len), build,
                            donate=(1,))

    def _staged_trunc_fn(self, cap: int):
        """Prefix view of the finished staging cache — runs on the prefill
        device, bounding the cross-device transfer to O(cap) ring bytes per
        leaf instead of O(max_len).  Not donated: slicing cannot reuse the
        input buffers, so donation would only warn."""
        from repro.models import truncate_rings
        max_len = self.max_len

        def build():
            def fn(cache):
                return truncate_rings(cache, cap, max_len)
            return fn
        return self._jitted(("staged_trunc", cap), build)

    def _staged_reset_fn(self):
        """Recycle a used staging cache to the fresh-bind state (donated:
        the reset rewrites it in place on the prefill device)."""
        from repro.models import reset_row

        def build():
            def fn(cache):
                return reset_row(cache, 0)
            return fn
        return self._jitted(("staged_reset",), build, donate=(0,))

    def _handoff_fn(self, pool_size: int, cap: int):
        """Install a transferred staging entry into pool row ``slot`` and
        commit its first output token to the device token vector — the
        dual-device twin of the in-pool ``emit`` scatter."""
        from repro.models import handoff_row
        max_len = self.max_len

        def build():
            def fn(pool, entry, toks, slot, first):
                pool = handoff_row(pool, entry, slot, cap, max_len)
                return pool, toks.at[slot].set(first)
            return fn
        return self._jitted(("handoff", pool_size, cap), build,
                            donate=(0, 2))

    # -- elastic binding (HEG affinity / backpressure / hit fallbacks) --------
    def _stage_for(self, req: Request, seq_start: int) -> bool:
        """Decide (once, stickily) whether this request prefills on the
        prefill device or co-locates on the decode device."""
        rid = req.id
        dec = self._stage_decision.get(rid)
        if dec is not None:
            return dec
        stage = self.dual_device
        if stage and self._hit.get(rid, 0) > 0:
            stage = False
            self.colocated_hits += 1
        if stage and len(self._staged) >= self.prefill_inflight_max:
            stage = False
            self.colocated_backpressure += 1
        if stage and self.heg is not None and self._price_lanes:
            # affinity/ETC fallback: co-locate only when the HEG prices the
            # prefill lane MEANINGFULLY worse (>5%) for this tail — the
            # tables put the two lanes within float noise of each other for
            # most shapes, and staging is the default the overlap pays for
            tail = max(req.prompt_len - seq_start, 1)
            if self.heg.prefill_time_estimate(tail, "npu") > \
                    1.05 * self.heg.prefill_time_estimate(tail, "igpu"):
                stage = False
                self.colocated_affinity += 1
        self._stage_decision[rid] = stage
        if stage:
            self._staged.add(rid)
            self.staged_prefills += 1
            self.prefill_inflight_peak = max(self.prefill_inflight_peak,
                                             len(self._staged))
        return stage

    # -- staged prefill drive -------------------------------------------------
    def _upload_prompt_pf(self, req: Request):
        """Pow-2-padded prompt tokens resident on the PREFILL device
        (the decode-device twin lives in ``_tok_dev``)."""
        rid = req.id
        buf = self._tok_dev_pf.get(rid)
        if buf is None:
            np = self._np
            toks = np.asarray(req.tokens, np.int32).reshape(1, -1)
            pad = np.zeros((1, _next_pow2(max(toks.shape[1], 1))), np.int32)
            pad[:, :toks.shape[1]] = toks
            buf = self._tok_dev_pf[rid] = self._jax.device_put(
                pad, self.prefill_device)
        return buf

    def _ensure_staged_at(self, req: Request, seq_start: int):
        """Staging cache positioned at ``seq_start`` — rebuilt (replaying
        the already-prefetched prefix) after a discard-style preemption
        reset the scheduler's chunk progress.  Reuses the ``_scratch``
        bookkeeping so every teardown path already covers it."""
        from repro.models import init_cache
        rid = req.id
        if rid in self._scratch and self._scratch_pos[rid] == seq_start:
            return
        jax = self._jax
        if self._staging_free:
            cache = self._call(self._staged_reset_fn(),
                               self._staging_free.pop(),
                               rid=rid, stage="prefill")
            self.prefill_device_calls += 1
        else:
            with jax.default_device(self.prefill_device):
                cache = init_cache(self.cfg, self.params, 1, self.max_len,
                                   self.dtype, kv_dtype=self._kv_dtype_arg)
            # device_put is a no-op when default_device already placed it
            cache = jax.device_put(cache, self.prefill_device)
        self._scratch[rid] = cache
        self._scratch_pos[rid] = 0
        self._nxt_dev.pop(rid, None)
        if seq_start > 0:
            self._run_staged(req, 0, seq_start)

    def _run_staged(self, req: Request, start: int, n: int):
        if n <= 0:  # zero-length chunk: nothing ran, ``nxt`` never exists
            return
        rid = req.id
        jnp = self._jnp
        buf = self._upload_prompt_pf(req)
        pos = start
        for size in _pow2_buckets(n):
            fn = self._staged_extend_fn(size, buf.shape[1])
            nxt, self._scratch[rid] = self._call(
                fn, self._params_pf, self._scratch[rid], buf,
                jnp.int32(pos), rid=rid, stage="prefill")
            self.prefill_device_calls += 1
            pos += size
        self._scratch_pos[rid] = pos
        self.kv_bytes_prefill += n * self._kv_token_bytes
        self.prefill_forward_tokens += n
        if pos >= req.prompt_len:
            # first output token stays ON the prefill device: the one host
            # sync per prefill happens at the handoff, never per chunk
            self._nxt_dev[rid] = nxt

    def prefill_chunk(self, req: Request, seq_start: int, tokens: int,
                      now: float) -> None:
        if req.tokens is None or req.id in self._quarantined:
            return
        self._begin_prefill(req)
        if not self._stage_for(req, seq_start):
            super().prefill_chunk(req, seq_start, tokens, now)
            return
        self._track_prefill(req.id)
        try:
            self._ensure_staged_at(req, seq_start)
            self._run_staged(req, seq_start, tokens)
        except FaultError as e:
            self._record_flow_fault(req, e, "prefill")

    # -- KV handoff (prefill device -> decode pool) ---------------------------
    def _prefill_done(self, req: Request, now: float) -> None:
        rid = req.id
        if rid not in self._staged:
            self._stage_decision.pop(rid, None)
            return super()._prefill_done(req, now)
        jax, jnp = self._jax, self._jnp
        nxt = self._nxt_dev.pop(rid, None)
        cache = self._scratch.pop(rid, None)
        self._scratch_pos.pop(rid, None)
        self._staged.discard(rid)
        self._stage_decision.pop(rid, None)
        if req.tokens is None or nxt is None or cache is None:
            # staged prefill made entirely of zero-length chunks: no
            # program ran, no pool slot was ever bound — nothing to hand off
            return
        # bound the transfer to the prompt's pow-2 ring prefix (prefill
        # positions never wrap: prompt_len <= max_len by engine contract)
        cap = min(_next_pow2(max(req.prompt_len, 1)), self.max_len)
        entry = cache
        if cap < self.max_len:
            entry = self._call(self._staged_trunc_fn(cap), cache,
                               rid=rid, stage="prefill")
        # async dispatch: both puts ENQUEUE transfers behind the prefill
        # device's compute chain — nothing here blocks the decode queue,
        # and the install below orders after them by data dependency
        entry = jax.device_put(entry, self.decode_device)
        first_dev = jax.device_put(nxt, self.decode_device)
        # the staging cache is NOT consumed by the transfer (device_put
        # and truncation both copy): recycle it for the next staged
        # prefill instead of paying a fresh init_cache
        if len(self._staging_free) < self.prefill_inflight_max:
            self._staging_free.append(cache)
        if rid not in self._slot:
            self._alloc_slot(rid)
        slot = self._slot[rid]
        fn = self._handoff_fn(self.pool_slots, cap)
        self._pool, self._toks = self._call(
            fn, self._pool, entry, self._toks, jnp.int32(slot), first_dev,
            rid=rid, stage="prefill")
        self.handoff_device_calls += 1
        self.kv_bytes_handoff += cap * self._kv_token_bytes
        # the ONE host sync of this prefill: waits on the prefill device's
        # dependency chain only (decode segments keep their own queue)
        with self.phases("backend.fetch", "host_fetch_s", rid=rid):
            first = int(nxt)
        self.host_syncs += 1
        self.prefill_host_syncs += 1
        self._slot_pos[slot] = req.prompt_len
        # donor indexing mirrors the in-pool branch (same wrap gate), so
        # staged prompts land on the decode pool as prefix sources too
        if self._prefix is not None \
                and req.prompt_len + req.max_new_tokens <= self.max_len:
            path, evicted = self._prefix.insert(_prompt_key(req))
            for node in path:
                self._set_source(node, ("slot", slot))
            for node in evicted:
                self._set_source(node, None)
        self._commit_first(req, first)

    def _drop_flow_state(self, rid: int) -> None:
        # mid-prefill abort / quarantine / release of a staged flow: the
        # staging cache rides in _scratch (cleared by super), the rest here
        self._staged.discard(rid)
        self._stage_decision.pop(rid, None)
        self._tok_dev_pf.pop(rid, None)
        super()._drop_flow_state(rid)

    def stats(self) -> dict:
        out = super().stats()
        out.update({
            "dual_device": self.dual_device,
            "prefill_device": str(self.prefill_device),
            "decode_device": str(self.decode_device),
            "staged_prefills": self.staged_prefills,
            "prefill_inflight_peak": self.prefill_inflight_peak,
            "handoff_device_calls": self.handoff_device_calls,
            "kv_bytes_handoff": self.kv_bytes_handoff,
            "colocated_hits": self.colocated_hits,
            "colocated_backpressure": self.colocated_backpressure,
            "colocated_affinity": self.colocated_affinity,
        })
        return out
