"""Block-size helpers shared by the Pallas kernels.

A TPU block's last two dims must each be a multiple of the (8, 128) vreg
tile or span the whole array dim; ``lane_block`` picks sizes that obey the
128-lane rule on the chip and in interpret mode alike, so the CPU tests
exercise the grid the chip compiles.
"""
from __future__ import annotations

LANES = 128


def clamp_block(extent: int, block: int) -> int:
    """Largest block size <= ``block`` that divides ``extent``.

    The kernel grids require the tiled extent to be an exact multiple of the
    block; the historical defaults (512/128) silently assumed ring/prompt
    extents at least that large.  Clamping to a divisor keeps tiny-config and
    small ``max_len`` paths on a valid grid instead of tripping the
    divisibility assert."""
    if extent <= 0:
        raise ValueError(f"cannot tile empty extent {extent}")
    block = max(1, min(block, extent))
    while extent % block:
        block -= 1
    return block


def lane_block(extent: int, block: int) -> int:
    """Block for an axis that some operand carries as its LAST dim: the
    largest multiple of 128 that is <= ``max(block, 128)`` and divides
    ``extent`` — or the whole extent when it is not a multiple of 128 (a
    full-extent block is always legal)."""
    if extent <= 0:
        raise ValueError(f"cannot tile empty extent {extent}")
    if extent % LANES:
        return extent
    b = max(block // LANES, 1) * LANES
    while extent % b:
        b -= LANES
    return b


def kv_extent(extent: int, kv_limit, block: int) -> tuple:
    """``(tiled extent, block)`` of a ring axis whose live prefix lies
    below ``kv_limit``: the limit is rounded up to whole 128-lane blocks —
    the extra ring slots hold ``slot_pos`` -1 or future positions, which
    the position mask drops — and clipped to the ring."""
    eff = extent if kv_limit is None else \
        min(extent, -(-max(int(kv_limit), 1) // LANES) * LANES)
    return eff, lane_block(eff, block)
