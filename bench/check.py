"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the flows
sent in the window and finished is drawn from the seed: the longest one
(prompt plus output), every flow whose prefill was preempted that the
draw reaches first, then others in a seeded order, until the sample holds
``MIN_FLOWS`` flows and ``MIN_TOKENS`` served tokens.  The float32 reference (``bench/reference.py``,
matmul precision "highest") runs once over each prompt with its served
tokens, and each served token's reference logit is compared with the
reference's largest at that position.  The number compared is the widest
such gap over the sample, against the cell's limit
(``bench/cells/<cell>.json``, ``widest_gap_limit``).

The control (``--control 1``) puts the reference in the program's place,
computed with float8_e4m3fn operands (per-tensor scales, float32
accumulation): the precision step below the configuration's bfloat16.  At
each position of the same sequences it reads the reference's gap of the
token the control ranks first.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.loadgen import seed_rng

MIN_TOKENS = 384  # served tokens the sample holds at least
MIN_FLOWS = 24  # and flows: each first token follows an unseen prompt
MAX_FLOWS = 48
SEQ_BUCKET = 1024  # reference sequence lengths are padded to a multiple
ROW_TOKENS = 4096  # sequences of one bucket run together up to this
POS_BUCKET = 512  # scored positions per sequence are padded to this


def sample(flows: list, seed: int) -> List[dict]:
    """``[{"prompt": ndarray, "out": [int], "preempted": bool}]``."""
    done = [f for f in flows if f["status"] == "completed" and f["out"]]
    if not done:
        return []
    order = seed_rng(seed, "check").permutation(len(done))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i]["prompt"]) + len(done[i]["out"]))
    ranked = [longest] + [i for i in order if i != longest]
    ranked = ranked[:1] + sorted(ranked[1:],
                                 key=lambda i: not done[i]["preempted"])
    out, tokens = [], 0
    for i in ranked:
        if (tokens >= MIN_TOKENS and len(out) >= MIN_FLOWS) \
                or len(out) >= MAX_FLOWS:
            break
        out.append(done[i])
        tokens += len(done[i]["out"])
    return out


def _scorers(cfg, operand_dtype):
    import jax
    import jax.numpy as jnp
    from bench.reference import head_summary, reference_hidden

    def rows(h, pos):
        return jax.vmap(lambda hb, pb: hb[pb])(h, pos).reshape(
            -1, h.shape[-1])

    @jax.jit
    def ref(params, toks, pos, tgt):
        h = rows(reference_hidden(cfg, params, toks), pos)
        return head_summary(params, h, tgt.reshape(-1))

    @jax.jit
    def ctl(params, toks, pos):
        hc = rows(reference_hidden(cfg, params, toks,
                                   operand_dtype=operand_dtype), pos)
        _, arg_c, _ = head_summary(params, hc, jnp.zeros(hc.shape[0],
                                                         jnp.int32),
                                   operand_dtype=operand_dtype)
        h = rows(reference_hidden(cfg, params, toks), pos)
        best, _, t = head_summary(params, h, arg_c)
        return best, arg_c, t

    return ref, ctl


def batches(picked: List[dict]):
    """Sequences (prompt + served tokens but the last) padded to a
    multiple of ``SEQ_BUCKET`` and grouped ``ROW_TOKENS // S`` at a time:
    ``(toks (B, S), pos (B, POS_BUCKET), tgt (B, POS_BUCKET), n [B])``."""
    by_len = {}
    for f in picked:
        seq = len(f["prompt"]) + len(f["out"]) - 1
        S = -(-seq // SEQ_BUCKET) * SEQ_BUCKET
        by_len.setdefault(S, []).append(f)
    for S, group in sorted(by_len.items()):
        B = max(ROW_TOKENS // S, 1)
        for i in range(0, len(group), B):
            toks = np.zeros((B, S), np.int32)
            pos = np.zeros((B, POS_BUCKET), np.int32)
            tgt = np.zeros((B, POS_BUCKET), np.int32)
            ns = []
            for b, f in enumerate(group[i:i + B]):
                prompt, out = np.asarray(f["prompt"]), list(f["out"])
                P, n = len(prompt), len(out)
                toks[b, :P] = prompt
                toks[b, P:P + n - 1] = out[:-1]
                pos[b, :n] = np.arange(P - 1, P - 1 + n)
                tgt[b, :n] = out
                ns.append(n)
            yield toks, pos, tgt, ns


def compare(cfg, params, picked: List[dict], *, control: bool = False
            ) -> dict:
    """Widest gap of the served tokens (and, with ``control``, of the
    control's tokens) below the reference's best logit."""
    import jax
    import jax.numpy as jnp
    ref, ctl = _scorers(cfg, jnp.float8_e4m3fn)
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for toks, pos, tgt, ns in batches(picked):
            args = (params, jnp.asarray(toks), jnp.asarray(pos))
            best, arg, t = (np.asarray(a).reshape(pos.shape)
                            for a in ref(*args, jnp.asarray(tgt)))
            for b, n in enumerate(ns):
                gap = np.where(arg[b, :n] == tgt[b, :n], 0.0,
                               np.maximum(best[b, :n] - t[b, :n], 0.0))
                worst = max(worst, float(gap.max()))
                n_tok += n
            if control:
                best, _, t = (np.asarray(a).reshape(pos.shape)
                              for a in ctl(*args))
                for b, n in enumerate(ns):
                    worst_ctl = max(worst_ctl, float(
                        np.maximum(best[b, :n] - t[b, :n], 0.0).max()))
    res = {"widest_gap": worst, "flows": len(picked), "tokens": n_tok,
           "distinct_share": float(np.mean([len(set(f["out"])) / len(f["out"])
                                            for f in picked] or [0.0]))}
    if control:
        res["control_widest_gap"] = worst_ctl
    return res
