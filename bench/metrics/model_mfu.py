"""Model FLOPs the window's committed tokens required, over the window's
seconds times the chip's bf16 peak, %.

Counted from the configuration and the traffic alone (``bench/flops.py``):
a token decoded at position p (the prompt's tokens at 0..P-1) costs
``token_flops(p + 1)``, and a prompt whose first token came in the window
costs ``prompt_flops(P)``.  Work the program repeats or skips does not
count, so this cannot pass 100% unless time is left out."""
from bench import flops
from bench.window import walls_in


def read(rec):
    cfg, peaks = rec["cfg"], rec["peaks"]
    if not peaks:
        return None
    total = 0
    for f, i in walls_in(rec, rec["ws"], rec["we"]):
        P = len(f["prompt"])
        if i == 0:
            total += flops.prompt_flops(cfg, P)
        else:
            total += flops.token_flops(cfg, P + i)
    seconds = rec["we"] - rec["ws"]
    return 100.0 * total / (seconds * peaks["bf16_flops_per_s"])
