"""Per-layer decode/prefill state (KV caches, SSM states).

Layout mirrors the parameter layout of ``transformer.py``:

    cache = {
      "pos":   (B,) int32     next absolute position to write,
      "head":  (state_0, ...) unrolled leading layers,
      "blocks": {pos_idx: stacked_state}   scanned pattern groups (leading R),
      "tail":  (state_0, ...) unrolled trailing layers,
      ["enc_out": (B, F, d)]  encoder output (enc-dec models),
    }

Attention state is a ring buffer of ``alloc`` slots; ``slot_pos`` stores each
slot's absolute position (-1 = empty) so sliding windows and RoPE stay
correct after wrap-around.

Quantized pools (DESIGN.md §11): ``kv_dtype="int8"`` stores the ``k``/``v``
ring payload as symmetric int8 with per-(ring slot, kv head) float32 scales
(``k_scale``/``v_scale``, shape (B, alloc, Hkv)).  The scale leaves carry the
same ring axis as the payload, so every view/write helper in this module
(ring views, the decode step's in-place write, prefix copies) treats them as
ordinary ring payload and the elastic-dispatch + prefix-cache machinery
works unchanged on quantized pools.  Dequantization happens at the attention read
(``transformer._attn_mix_extend`` or in-kernel in the Pallas backend), never
as a separate pass.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# smallest representable scale: keeps all-zero K/V rows exactly zero after
# the round trip instead of dividing by zero
_QUANT_EPS = 1e-8


def kv_supports_int8(cfg) -> bool:
    """int8 KV quantization covers the standard k/v ring layout; MLA caches
    store a latent (``c``/``kr``) whose per-head scale axis does not exist."""
    return not cfg.use_mla


def quantize_kv(x):
    """Symmetric per-(…, head) int8 quantization of a K/V tensor whose
    trailing axis is ``head_dim``: returns ``(q int8, scale f32)`` with
    ``scale = max|x| / 127`` over the head_dim axis (shape = x.shape[:-1]).
    Exactly invertible to within ``scale/2`` per element — the bound the
    round-trip tests assert."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, _QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Fuse-friendly inverse of :func:`quantize_kv`: ``q * scale`` broadcast
    over the head_dim axis.  Called inside the jitted attention program (XLA
    fuses it into the score matmul's operand read) or inside the Pallas
    kernels — never materialized pool-wide."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def attn_alloc_len(cfg, max_len: int, window: Optional[int]) -> int:
    w = window if window is not None else cfg.sliding_window
    return min(max_len, w) if w is not None else max_len


def init_layer_state(cfg, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16, window: Optional[int] = None,
                     cross_len: int = 0, kv_dtype: Optional[str] = None) -> dict:
    quant = kv_dtype == "int8"
    if kind == "attn":
        if cfg.use_mla:
            if quant:
                raise NotImplementedError(
                    "int8 KV quantization is per-(slot, kv head); MLA caches "
                    "a latent without a head axis (kv_supports_int8)")
            alloc = attn_alloc_len(cfg, max_len, window)
            st = {
                "c": jnp.zeros((batch, alloc, cfg.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, alloc, cfg.qk_rope_head_dim), dtype),
                "slot_pos": jnp.full((batch, alloc), -1, jnp.int32),
            }
        else:
            alloc = attn_alloc_len(cfg, max_len, window)
            hkv, hd = cfg.num_kv_heads, cfg.head_dim
            payload_dtype = jnp.int8 if quant else dtype
            st = {
                "k": jnp.zeros((batch, alloc, hkv, hd), payload_dtype),
                "v": jnp.zeros((batch, alloc, hkv, hd), payload_dtype),
                "slot_pos": jnp.full((batch, alloc), -1, jnp.int32),
            }
            if quant:
                st["k_scale"] = jnp.zeros((batch, alloc, hkv), jnp.float32)
                st["v_scale"] = jnp.zeros((batch, alloc, hkv), jnp.float32)
        if cross_len:
            hkv, hd = cfg.num_kv_heads, cfg.head_dim
            st["xk"] = jnp.zeros((batch, cross_len, hkv, hd), dtype)
            st["xv"] = jnp.zeros((batch, cross_len, hkv, hd), dtype)
        return st
    if kind == "rwkv6":
        H = cfg.d_model // cfg.ssm_head_dim
        return {
            "wkv": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_head_dim),
                             jnp.float32),
            "shift_tm": jnp.zeros((batch, cfg.d_model), dtype),
            "shift_cm": jnp.zeros((batch, cfg.d_model), dtype),
        }
    if kind == "rglru":
        return {
            "h": jnp.zeros((batch, cfg.lru_width), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv1d_width - 1, cfg.lru_width),
                              dtype),
        }
    raise ValueError(kind)


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))


# ============================ slot-pool helpers =============================
# A *slot pool* is an ordinary cache (init_cache) whose batch dimension is a
# pool of independent decode slots: requests are bound to a slot when their
# prefill completes and freed when they finish, so one masked decode step
# serves the whole pool in a single device call (DESIGN.md §3).
#
# The batch axis is 0 for the "pos"/"head"/"tail"/"enc_out" sections but 1
# for "blocks" (scanned groups carry a leading repeats axis), so the helpers
# below map section-aware functions over cache pytrees.

def _map_batched(fn0, fn1, *caches):
    """tree_map ``fn0`` over batch-axis-0 sections and ``fn1`` over the
    batch-axis-1 ``blocks`` section of one or more structurally-equal caches."""
    out = dict(caches[0])
    for key in ("pos", "head", "tail", "enc_out"):
        if key in caches[0]:
            out[key] = jax.tree_util.tree_map(fn0, *[c[key] for c in caches])
    out["blocks"] = jax.tree_util.tree_map(fn1, *[c["blocks"] for c in caches])
    return out


def write_slot(pool, one, slot):
    """Scatter a batch-1 cache into batch row ``slot`` of the pool cache
    (prefill-to-decode handoff).  ``slot`` may be a traced int32."""
    return _map_batched(lambda p, o: p.at[slot].set(o[0]),
                        lambda p, o: p.at[:, slot].set(o[:, 0]),
                        pool, one)


def read_row(pool, slot):
    """Batch-1 *row view* of pool row ``slot`` — the gather that lets any
    whole-cache function (``models.extend``) run against a single pool row.
    ``slot`` may be a traced int32.  Inside one jitted function whose pool
    argument is donated, a ``read_row`` -> update -> ``write_row_slice``
    round trip keeps all other rows aliased in place, so in-pool prefill
    (DESIGN.md §7) writes each chunk's KV into the live pool exactly once."""
    import jax.lax as lax
    return _map_batched(lambda p: lax.dynamic_slice_in_dim(p, slot, 1, axis=0),
                        lambda p: lax.dynamic_slice_in_dim(p, slot, 1, axis=1),
                        pool)


# The quantization scale leaves (k_scale/v_scale, (B, alloc, Hkv)) carry the
# same ring axis as k/v, so they join both payload families: ring-sliced by
# every view/write helper, and COW-preserved (not zeroed) by reset_row — a
# stale scale under a -1 slot_pos is as invisible as the stale payload.
_ATTN_PAYLOAD = frozenset({"k", "v", "c", "kr", "xk", "xv",
                           "k_scale", "v_scale"})
_RING_PAYLOAD = frozenset({"k", "v", "c", "kr", "slot_pos",
                           "k_scale", "v_scale"})


def write_row_slice(pool, one, slot, start, c):
    """Row-targeted chunk write-back (in-pool prefill, DESIGN.md §7):
    scatter ONLY the ``c`` ring-buffer positions ``[start, start+c)`` (mod
    alloc, tail-clipped exactly like the extend write itself) of batch-1
    cache ``one`` into pool row ``slot``, plus the small non-positional
    state (``pos``, recurrent/shift/conv).  Per chunk this moves O(c) KV
    bytes instead of O(alloc); the full-row ``write_slot`` scatter remains
    only in the scratch+bind baseline.  ``slot``/``start`` may be traced."""
    from jax.tree_util import DictKey, tree_map_with_path

    def fix(axis):
        def f(path, p, o):
            name = path[-1].key if isinstance(path[-1], DictKey) else ""
            if name in _RING_PAYLOAD:
                alloc = p.shape[axis + 1]
                n = min(c, alloc)
                idx = (start + (c - n) + jnp.arange(n)) % alloc
                if axis == 0:
                    return p.at[slot, idx].set(o[0, idx])
                return p.at[:, slot, idx].set(o[:, 0, idx])
            return p.at[slot].set(o[0]) if axis == 0 \
                else p.at[:, slot].set(o[:, 0])
        return f

    out = dict(pool)
    out["pos"] = pool["pos"].at[slot].set(one["pos"][0])
    for key in ("head", "tail"):
        out[key] = tree_map_with_path(fix(0), pool[key], one[key])
    out["blocks"] = tree_map_with_path(fix(1), pool["blocks"], one["blocks"])
    return out


def truncate_rings(one, kv_limit, full):
    """Static prefix view of a cache: ring leaves that can never wrap
    (``alloc`` equals ``full``, the cache's build-time ``max_len`` —
    positions stay below it, so no sliding window shrank the ring) are
    sliced to their first ``kv_limit`` slots.  While positions stay below
    ``kv_limit`` the dropped slots are all empty (``slot_pos == -1`` after
    ``reset_row``), so attention output is unchanged — but the program only
    reads and scores O(live prefix) keys instead of O(alloc).  Windowed
    leaves (``alloc < full``) may wrap and keep their full ring.

    Batch-size agnostic: the alloc axis is addressed relative to the
    section layout (axis 1 for ``head``/``tail``, 2 for ``blocks``), so the
    same view serves in-pool prefill (batch-1 rows, DESIGN.md §7), prefix
    snapshots and the staged-prefill handoff.  Pooled decode takes no view:
    it reads the same extent from the carried pool (``write_decode_step``)."""
    from jax.tree_util import DictKey, tree_map_with_path

    if not full or kv_limit >= full:
        return one

    def fix(axis):
        def f(path, x):
            name = path[-1].key if isinstance(path[-1], DictKey) else ""
            if name in _RING_PAYLOAD and x.shape[axis] == full:
                return x[(slice(None),) * axis + (slice(0, kv_limit),)]
            return x
        return f

    out = dict(one)
    for key in ("head", "tail"):
        out[key] = tree_map_with_path(fix(1), one[key])
    out["blocks"] = tree_map_with_path(fix(2), one["blocks"])
    return out


def reset_row(pool, slot):
    """Invalidate batch row ``slot`` for rebinding (slot-at-prefill-start):

    * attention ``slot_pos`` rows become -1, which every attention mask
      treats as empty — the (large) K/V payload of the previous occupant is
      NOT rewritten, making a rebind O(alloc) instead of O(alloc * d);
    * recurrent / shift / conv states and ``pos`` are zeroed (they
      accumulate, so masking alone cannot neutralize them).

    Jitted with the pool donated this is a handful of small in-place row
    scatters — the zero-copy replacement for the old full-row bind scatter.
    (``enc_out`` is per-request encoder output and is left untouched; the
    real backend serves text-only decoders.)"""
    from jax.tree_util import DictKey, tree_map_with_path

    def fix(axis):
        def f(path, x):
            name = path[-1].key if isinstance(path[-1], DictKey) else ""
            if name in _ATTN_PAYLOAD:
                return x
            val = -1 if name == "slot_pos" else 0
            return x.at[slot].set(val) if axis == 0 else x.at[:, slot].set(val)
        return f

    out = dict(pool)
    out["pos"] = pool["pos"].at[slot].set(0)
    for key in ("head", "tail"):
        out[key] = tree_map_with_path(fix(0), pool[key])
    out["blocks"] = tree_map_with_path(fix(1), pool["blocks"])
    return out


def _mask_prefix_view(one, hit, cap):
    """Clamp a batch-1 ring view to exactly its first ``hit`` positions:
    ``slot_pos`` entries at and beyond ``hit`` flip to -1 (empty — attention
    masks them out even though the K/V payload still holds donor bytes, the
    same copy-on-write trick ``reset_row`` plays on a whole row) and ``pos``
    becomes ``hit``.  ``hit`` may be traced; ``cap`` is the view's static
    ring alloc (``hit <= cap``)."""
    from jax.tree_util import DictKey, tree_map_with_path

    live = jnp.arange(cap) < hit

    def fix(path, x):
        name = path[-1].key if isinstance(path[-1], DictKey) else ""
        if name == "slot_pos":
            return jnp.where(live, x, -1)
        return x

    out = dict(one)
    for key in ("head", "tail"):
        out[key] = tree_map_with_path(fix, one[key])
    out["blocks"] = tree_map_with_path(fix, one["blocks"])
    out["pos"] = jnp.zeros_like(one["pos"]) + jnp.int32(hit)
    return out


def copy_prefix_rows(pool, src, dst, hit, hit_cap, full):
    """Shared-prefix KV reuse, row-to-row (DESIGN.md §10): gather ring
    positions ``[0, hit)`` of donor row ``src`` into a freshly
    ``reset_row``-ed row ``dst``, leaving ``dst`` exactly as if tokens
    ``[0, hit)`` had been prefilled into it — O(hit · KV-copy) instead of
    O(hit · forward).

    ``hit_cap`` is the static pow-2 bucket covering ``hit`` (bounds the jit
    key space to O(log max_len) shapes); the traced ``hit`` masks the
    ``[hit, hit_cap)`` overhang — donor ring slots whose K/V ride along but
    whose ``slot_pos`` is flipped to -1, so they are invisible to attention
    and simply overwritten by the consumer's tail prefill.  Exact only for
    never-wrapping pure-attention rings (``prefixcache.prefix_reuse_
    supported``); ``src``/``dst``/``hit`` may be traced, ``src != dst``.
    Under jit with the pool donated this is a bounded row gather + row
    scatter — no forward pass, no full-ring traffic."""
    pool = reset_row(pool, dst)
    eff = min(hit_cap, full) if full else hit_cap
    view = truncate_rings(read_row(pool, src), eff, full)
    view = _mask_prefix_view(view, hit, eff)
    return write_row_slice(pool, view, dst, 0, eff)


def snapshot_prefix(pool, src, depth_cap, full):
    """Detach the leading ``depth_cap`` ring slots of row ``src`` as an
    immutable batch-1 prefix entry (the refcounted shared-prefix store,
    DESIGN.md §10): taken at slot-rebind time, the instant a donor row's
    buffers would otherwise be reused.  NOT donated — the pool must survive
    — and deliberately tiny: O(depth_cap) ring bytes per leaf."""
    eff = min(depth_cap, full) if full else depth_cap
    return truncate_rings(read_row(pool, src), eff, full)


def paste_prefix(pool, entry, dst, hit, hit_cap, entry_alloc, full):
    """Consume a :func:`snapshot_prefix` store entry: re-truncate it to the
    consumer's ``hit_cap`` bucket, mask to the traced ``hit``, and scatter
    into a freshly ``reset_row``-ed row ``dst`` — the store-sourced twin of
    :func:`copy_prefix_rows` (``hit <= hit_cap <= entry_alloc``)."""
    pool = reset_row(pool, dst)
    eff = min(hit_cap, entry_alloc)
    view = truncate_rings(entry, eff, entry_alloc)
    view = _mask_prefix_view(view, hit, eff)
    return write_row_slice(pool, view, dst, 0, eff)


def handoff_row(pool, entry, slot, entry_alloc, full):
    """Install a staged prefill row into the decode pool (dual-device KV
    handoff, DESIGN.md §14): ``entry`` is a :func:`truncate_rings` view of
    a batch-1 staging cache whose prefill ran to completion on the prefill
    device, already ``device_put`` onto the pool's device.

    ``reset_row`` first invalidates the previous occupant — ``slot_pos``
    beyond ``entry_alloc`` would otherwise leak the old row's ring overhang
    into attention — then the entry's ring prefix, positions, and
    recurrent/shift/conv state land verbatim via the same ring-indexed
    scatter in-pool prefill uses.  Unlike :func:`paste_prefix` there is no
    ``_mask_prefix_view``: the staging cache's ``slot_pos``/``pos`` are
    already exact (every position below ``entry_alloc`` live, everything
    else -1 from init), which also keeps the copy correct for windowed and
    recurrent leaves the mask helper cannot shape."""
    pool = reset_row(pool, slot)
    eff = min(entry_alloc, full) if full else entry_alloc
    return write_row_slice(pool, entry, slot, 0, eff)


def copy_into_prefix(new, old, p):
    """Copy the ``p`` batch rows of pool cache ``old`` into the first ``p``
    rows of the (larger) freshly-initialized pool ``new`` (pool doubling).

    Runs un-jitted on purpose: pool growth is the one place where donated
    decode buffers must NOT be consumed — ``old`` may be the backend's live
    pool, and ``.at[].set`` outside jit always materializes fresh arrays, so
    the grown pool is safe to donate from the next decode call onward."""
    return _map_batched(lambda n, o: n.at[:p].set(o),
                        lambda n, o: n.at[:, :p].set(o), new, old)


def select_rows(mask, new, old):
    """Masked row update of per-row state (batch axis 0, as a pooled decode
    step reads it): row ``b`` of the result is ``new``'s where ``mask[b]``
    else ``old``'s, so inactive slots keep their positions and recurrent /
    shift / conv states untouched.  Ring payload never goes through here:
    :func:`write_decode_step` drops an inactive row's write instead."""
    def sel(n, o):
        return jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    return jax.tree_util.tree_map(sel, new, old)


def write_decode_step(st, vals, pos, *, layer, active, kv_limit, full):
    """In-place K/V write of one layer of a pooled decode step whose layer
    loop carries the pool (DESIGN.md §9).

    ``st`` holds the layer's ring leaves whole, as the loop carries them:
    ``(P, alloc, ...)`` for an unrolled layer (``layer`` None) or
    ``(R, P, alloc, ...)`` for a scanned group, ``layer`` being the traced
    index into ``R``; other leaves (cross-attention K/V) arrive already cut
    to the step's rows and pass through.  ``vals`` maps ring leaf names to
    this step's ``(rows, 1, ...)`` values for pool rows ``[0, rows)`` and
    ``pos`` is their ``(rows, 1)`` positions.  One scatter a leaf writes
    them at ``(layer, row, pos % alloc)``; an inactive row's index points
    past the pool and the scatter drops it, so a masked slot's ring is
    never touched.  Under jit with the pool donated the scatter updates
    the carried buffer in place: a step writes one position a row, not the
    slab.

    Returns ``(st, view)``: the updated leaves, and the window of each that
    attention reads, sliced from the updated buffer so the step's own token
    is in it: rows ``[0, rows)`` and, as in :func:`truncate_rings`, the
    first ``kv_limit`` slots of a ring that cannot wrap (``alloc == full``)
    or the whole of any other (``kv_limit >= full`` is the ring-wrap
    fallback: an ordinary read extent, not a copy)."""
    bounded = bool(full) and kv_limit is not None and kv_limit < full
    rows = pos.shape[0]
    lead = () if layer is None else (layer,)
    out, view = dict(st), dict(st)
    for name, leaf in st.items():
        if name not in _RING_PAYLOAD:
            continue
        pool_rows, alloc = leaf.shape[len(lead)], leaf.shape[len(lead) + 1]
        if name in vals:
            row = jnp.where(active, jnp.arange(rows), pool_rows)
            idx = lead + (row, pos[:, 0] % alloc)
            leaf = leaf.at[idx].set(vals[name][:, 0], mode="drop")
        out[name] = leaf
        # one slice of exactly the window (not a layer slice sliced again),
        # so XLA fuses it into the read that consumes it
        ext = kv_limit if bounded and alloc == full else alloc
        view[name] = jax.lax.dynamic_slice(
            leaf, lead + (0,) * (leaf.ndim - len(lead)),
            (1,) * len(lead) + (rows, ext) + leaf.shape[len(lead) + 2:]
        ).reshape((rows, ext) + leaf.shape[len(lead) + 2:])
    return out, view
