"""Least time the decode attention of the traced interval needs, over the
device time of the Pallas ``decode_attention`` kernel's events, %.

Both sides cover the traced interval: the kernel's device time inside it,
and every token committed on the host inside the same interval, which the
trace's clock marks map onto the host clock (``bench.trace.to_host``).  A
token at position p reads the K and V of p + 1 positions in every layer
and does ``attn_flops(p + 1)``; the bound is the larger of bytes over the
chip's HBM bandwidth and FLOPs over its bf16 peak (``bench/flops.py``).
Decode steps the scheduler cancels at a segment boundary never launch, so
they are neither timed nor counted; the padding rows and positions a
launched step computes are timed and not counted, since no token needs
them.  A token is committed when its segment's block reaches the host, a
segment after its step ran: the two ends of the interval offset each
other.  Nothing to read (no kernel events, no decode tokens, no clock
mark) gives no value."""
from bench import flops
from bench.trace import to_host
from bench.window import walls_in

KERNEL = "decode_attention"


def read(rec):
    red, peaks = rec["trace"], rec["peaks"]
    span = to_host(red, rec["trace_host"])
    if span is None or not peaks:
        return None
    t_kernel = sum(s for d in red["devices"][:1] for n, s in d["ops"]
                   if KERNEL in n)
    if t_kernel <= 0:
        return None
    cfg, kv = rec["cfg"], rec["conf"].get("kv_dtype", "bf16")
    nbytes = nflops = 0
    for f, i in walls_in(rec, span[0], span[1], first=False):
        ctx = len(f["prompt"]) + i
        nbytes += flops.decode_attn_bytes(cfg, ctx, kv)
        nflops += flops.attn_flops(cfg, ctx)
    if not nbytes:
        return None
    t_min, _ = flops.roofline_s(nflops, nbytes, peaks)
    return 100.0 * t_min / t_kernel
