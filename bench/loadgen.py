"""Seeded traffic for one cell: closed-loop proactive agents and open-loop
reactive users, read from a traffic file (``bench/traffic/<mix>.json``) and
the cell's offered rate (``bench/cells/<cell>.json``).

Every seed gets the same multiset of reactive prompt lengths, output
lengths and inter-arrival gaps, in another order, and its own token ids.
Each agent sends the same sequence of sizes under every seed: a run's
window holds only a few requests of each agent, so an order drawn from the
seed would change the proactive work a window offers.  So the work a run
offers does not change with the seed; only which reactive request meets
which does.  Lengths are stratified quantiles of a clipped lognormal,
prompts rounded to the mix's grid (a finite set of prompt shapes, which
set-up can warm), gaps stratified quantiles of the exponential scaled to
the span they fill, so a span of ``s`` seconds at rate ``r`` holds exactly
``round(r * s)`` arrivals.

The schedule is open loop for reactive flows: each is due at a fixed
offset and is timed from that offset, however late the generator sends
it.  Proactive agents are closed loop: agent ``a`` sends its next request
as soon as its previous one has finished, or ``retry_after_s`` after the
engine refused it.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, stream); any integer seed."""
    words = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) % (1 << 64)] + words)


def lognormal_quantiles(spec: dict, n: int, grid: int = 1) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal with the given median and
    sigma, clipped to [min, max] and rounded to a multiple of ``grid``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    x = np.round(x / grid) * grid
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(n: int, span: float) -> np.ndarray:
    """``n`` stratified exponential quantiles that sum to ``span``."""
    q = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return q * (span / q.sum())


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    grid = traffic["grid"]
    out = set()
    for kind in ("proactive", "reactive"):
        p = traffic[kind]["prompt"]
        out |= set(range(p["min"], p["max"] + 1, grid))
    return sorted(out)


@dataclasses.dataclass
class Flow:
    """One request: what is sent, when it was due, and what came back."""
    kind: str  # "reactive" | "proactive"
    prompt: np.ndarray  # (P,) int32 token ids
    max_new: int
    due: Optional[float] = None  # reactive: intended send, seconds from t0
    sent: Optional[float] = None  # actual send (host clock, seconds)
    intended: Optional[float] = None  # intended send (host clock, seconds)
    handle: object = None  # the front end's FlowHandle
    in_window: bool = False
    retry_at: Optional[float] = None  # proactive: when a refused agent
    #                                   sends again


class Traffic:
    """The flows of one run, generated lazily but deterministically."""

    def __init__(self, traffic: dict, rate: float, agents: int, vocab: int,
                 seed: int, window_s: float):
        self.spec = traffic
        self.rate = float(rate)
        self.agents = int(agents)
        self.vocab = int(vocab)
        self.window_s = float(window_s)
        self.preroll_s = float(traffic["preroll_s"])
        self.retry_after_s = float(traffic["proactive"]["retry_after_s"])
        grid = traffic["grid"]
        # distinct first tokens for every prompt of the run, warm-up
        # included: no two prompts share a prefix, so the prefix cache
        # never hits on this mix
        self._first = seed_rng(seed, "first").permutation(vocab)
        self._n_first = 0
        self._tok_rng = seed_rng(seed, "tokens")
        order = seed_rng(seed, "order")
        # proactive: one fixed multiset of sizes, permuted per agent in an
        # order that no seed changes
        pro = traffic["proactive"]
        m = int(pro["requests_per_agent"])
        pp = lognormal_quantiles(pro["prompt"], m, grid)
        po = lognormal_quantiles(pro["output"], m)
        agent_order = seed_rng(0, "agent sizes")
        self._agent_sizes = []
        for _ in range(self.agents):
            perm = agent_order.permutation(m)
            self._agent_sizes.append((pp[perm],
                                      po[agent_order.permutation(m)]))
        self._agent_next = [0] * self.agents
        # reactive: pre-roll, window and post-roll spans, each with a fixed
        # count of arrivals and a fixed multiset of sizes
        rea = traffic["reactive"]
        self.reactive: List[Flow] = []
        t = 0.0
        post_s = float(traffic["postroll_arrivals_s"])
        for span in (self.preroll_s, self.window_s, post_s):
            n = max(int(round(self.rate * span)), 1)
            gaps = order.permutation(exp_gaps(n, span))
            rp = order.permutation(lognormal_quantiles(rea["prompt"], n, grid))
            ro = order.permutation(lognormal_quantiles(rea["output"], n))
            # consecutive arrivals are the gaps apart; the last gap wraps
            # round, half before the first arrival and half after the
            # last, so the span holds exactly n of them
            due = t + gaps[-1] / 2 + np.concatenate([[0.0],
                                                     np.cumsum(gaps[:-1])])
            for d, p, o in zip(due, rp, ro):
                self.reactive.append(Flow("reactive", self.prompt(int(p)),
                                          int(o), due=float(d)))
            t += span

    def prompt(self, n: int) -> np.ndarray:
        """A fresh prompt of ``n`` tokens with a first token no other
        prompt of this run has."""
        if self._n_first >= self.vocab:
            raise RuntimeError("more prompts than vocabulary ids")
        first = self._first[self._n_first]
        self._n_first += 1
        rest = self._tok_rng.integers(0, self.vocab, n - 1)
        return np.concatenate([[first], rest]).astype(np.int32)

    def next_proactive(self, agent: int) -> Flow:
        sizes_p, sizes_o = self._agent_sizes[agent]
        j = self._agent_next[agent] % len(sizes_p)
        self._agent_next[agent] += 1
        return Flow("proactive", self.prompt(int(sizes_p[j])),
                    int(sizes_o[j]))


def agents_for(traffic: dict, pool_slots: int) -> int:
    """Closed-loop agent count: a share of the pool's slots, rounded
    down, at least one."""
    share = traffic["proactive"]["agents_per_slot"]
    return max(int(math.floor(share * pool_slots)), 1)


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None for no values."""
    if not values:
        return None
    v = sorted(values)
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])

