"""The traffic generator: seeded, and the same work for every seed."""
import json
import os

import numpy as np

from bench import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = json.load(open(os.path.join(HERE, "..", "traffic", "agent_mix.json")))


def _gen(seed, window=30.0, rate=2.0, agents=12):
    return loadgen.Traffic(MIX, rate, agents, 151936, seed, window)


def _flows(gen, per_agent=5):
    pro = [gen.next_proactive(a) for a in range(gen.agents)
           for _ in range(per_agent)]
    return gen.reactive + pro


def test_same_seed_same_schedule_and_prompts():
    a, b = _flows(_gen(2 ** 31 + 5)), _flows(_gen(2 ** 31 + 5))
    assert [f.due for f in a] == [f.due for f in b]
    assert [f.max_new for f in a] == [f.max_new for f in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_other_seed_other_order_and_tokens():
    a, b = _flows(_gen(7)), _flows(_gen(8))
    assert [f.due for f in a] != [f.due for f in b]
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b) if len(x.prompt) == len(y.prompt))


def test_every_seed_offers_the_same_work():
    """Sizes and gaps are one multiset per seed, in another order."""
    a, b = _gen(1), _gen(99)
    for key in (lambda f: len(f.prompt), lambda f: f.max_new):
        assert sorted(map(key, a.reactive)) == sorted(map(key, b.reactive))
    assert len(a.reactive) == len(b.reactive)
    pa = sorted(len(a.next_proactive(0).prompt) for _ in range(64))
    pb = sorted(len(b.next_proactive(3).prompt) for _ in range(64))
    assert pa == pb


def test_agents_send_the_same_sizes_under_every_seed():
    a, b = _gen(2 ** 33 + 1), _gen(17)
    for agent in range(a.agents):
        fa = [a.next_proactive(agent) for _ in range(6)]
        fb = [b.next_proactive(agent) for _ in range(6)]
        assert [(len(f.prompt), f.max_new) for f in fa] == \
            [(len(f.prompt), f.max_new) for f in fb]
        assert not np.array_equal(fa[0].prompt, fb[0].prompt)


def test_window_holds_rate_times_seconds_arrivals():
    g = _gen(3, window=40.0, rate=2.5)
    inside = [f for f in g.reactive
              if g.preroll_s <= f.due < g.preroll_s + 40.0]
    assert len(inside) == 100


def test_lengths_on_grid_and_clipped():
    g = _gen(4)
    for f in _flows(g, 20):
        spec = MIX[f.kind]
        assert spec["prompt"]["min"] <= len(f.prompt) <= spec["prompt"]["max"]
        assert len(f.prompt) % MIX["grid"] == 0
        assert spec["output"]["min"] <= f.max_new <= spec["output"]["max"]
    assert set(len(f.prompt) for f in _flows(g, 20)) <= \
        set(loadgen.prompt_lengths(MIX))


def test_no_two_prompts_share_a_first_token():
    g = _gen(5)
    firsts = [int(f.prompt[0]) for f in _flows(g, 30)]
    assert len(firsts) == len(set(firsts))


def test_agents_are_three_quarters_of_the_pool():
    assert loadgen.agents_for(MIX, 16) == 12
    assert loadgen.agents_for(MIX, 12) == 9


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert loadgen.percentile(v, 90) == 90
    assert loadgen.percentile(v, 95) == 95
    assert loadgen.percentile([], 90) is None
