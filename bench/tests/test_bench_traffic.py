"""The traffic generator: deterministic per seed, the stated laws, and
the same multiset of work for every seed."""

import json
from collections import Counter
from pathlib import Path

import pytest

from bench import traffic
from bench.tests.smoke import MIXES as TEST_MIXES

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    """A mix file of bench/traffic/, or one of the generator's test mixes
    (shared prefixes with STRICT sessions, a backlog)."""
    if name in TEST_MIXES:
        return TEST_MIXES[name]
    return json.loads((MIXES / f"{name}.json").read_text())


def test_same_seed_same_turns():
    m = mix("shared_prefix_strict")
    a = traffic.Generator(m, 2**33 + 5, 151936, 3968)
    b = traffic.Generator(m, 2**33 + 5, 151936, 3968)
    ta, tb = a.open_loop(400), b.open_loop(400)
    assert [(t.due_s, t.prompt, t.max_new_tokens, t.strict) for t in ta] == \
        [(t.due_s, t.prompt, t.max_new_tokens, t.strict) for t in tb]
    c = traffic.Generator(m, 2**33 + 6, 151936, 3968)
    assert [t.prompt for t in c.open_loop(400)] != [t.prompt for t in ta]


@pytest.mark.parametrize("name", ["shared_prefix_strict", "code_complete",
                                  "backlog"])
def test_every_seed_draws_the_same_multiset(name):
    """Within each block the draws are the law's quantiles, in a seeded
    order: two seeds send the same lengths."""
    m = mix(name)
    block = m["block"]
    outs = []
    for seed in (1, 2**31 + 17):
        g = traffic.Generator(m, seed, 49152, 3968)
        outs.append(sorted(g.output_len() for _ in range(4 * block)))
        assert sorted(g.prompt_len() for _ in range(block)) == sorted(
            traffic.lognormal_quantile(m["prompt"])(u)
            for u in traffic._quantiles(block))
    assert outs[0] == outs[1]


@pytest.mark.parametrize("name", ["shared_prefix_strict", "code_complete",
                                  "backlog"])
def test_lengths_within_stated_bounds(name):
    m = mix(name)
    g = traffic.Generator(m, 3, 49152, 3968)
    turns = (g.open_loop(300) if m["arrival"]["kind"] == "poisson"
             else [next(g.backlog()) for _ in range(200)])
    assert turns
    for t in turns:
        assert m["output"]["min"] <= t.max_new_tokens <= m["output"]["max"]
        assert len(t.prompt) + t.max_new_tokens <= 3968
        if t.index == 0:
            own = len(t.prompt) - (m["shared_prefix"]["tokens"]
                                   if "shared_prefix" in m else 0)
            assert m["prompt"]["min"] <= own <= m["prompt"]["max"]
    med = sorted(t.max_new_tokens for t in turns)[len(turns) // 2]
    assert abs(med - m["output"]["median"]) <= 0.15 * m["output"]["median"]


def test_chat_sessions_share_prefixes_and_grow():
    m = mix("shared_prefix_strict")
    g = traffic.Generator(m, 9, 151936, 3968)
    turns = g.open_loop(600)
    heads = Counter(tuple(t.prompt[:1024]) for t in turns if t.index == 0)
    assert len(heads) <= m["shared_prefix"]["count"]
    # Zipf(1): the most popular prompt opens the most sessions
    first = [t for t in turns if t.index == 0]
    by_pick = Counter(t.shared_prefix for t in first)
    assert by_pick.most_common(1)[0][0] == 0
    # turn k+1 extends turn k's prompt by a reply of turn k's output length
    by_sess = {}
    for t in turns:
        by_sess.setdefault(t.session, []).append(t)
    multi = [v for v in by_sess.values() if len(v) > 1]
    assert multi
    for ts in multi:
        ts.sort(key=lambda t: t.index)
        for a, b in zip(ts, ts[1:]):
            assert b.prompt[:len(a.prompt)] == a.prompt
            assert len(b.prompt) > len(a.prompt) + a.max_new_tokens
            assert b.due_s > a.due_s
            assert a.strict == b.strict
    # one session in four is STRICT
    share = sum(t.strict for t in first) / len(first)
    assert abs(share - m["strict_share"]) < 0.1


def test_unshared_prompts_share_no_page():
    g = traffic.Generator(mix("code_complete"), 4, 49152, 3968)
    turns = g.open_loop(120)
    heads = {tuple(t.prompt[:128]) for t in turns}
    assert len(heads) == len(turns)
    assert not any(t.strict for t in turns)


def test_arrivals_keep_the_stated_rate():
    m = mix("code_complete")
    g = traffic.Generator(m, 5, 49152, 3968)
    turns = g.open_loop(1000)
    rate = m["arrival"]["rate_per_s"]
    assert abs(len(turns) / 1000 - rate) < 0.05 * rate


def test_backlog_target():
    assert traffic.waiting_target(mix("backlog"), 32) == 64
    assert traffic.waiting_target(mix("shared_prefix_strict"), 32) is None
