"""The traffic generator: reproducible from its seed, lengths inside the
clips, the same work for every seed, bursts that keep the mean rate."""
from collections import Counter

import numpy as np
import pytest

import traffic

MIXES = ["chat-poisson", "rag-closed32"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = traffic.load_mix(name)
    a = traffic.make_plan(mix, 2 ** 33 + 5, 32000, 200)
    b = traffic.make_plan(mix, 2 ** 33 + 5, 32000, 200)
    c = traffic.make_plan(mix, 2 ** 33 + 6, 32000, 200)
    assert [(len(p.prompt), p.max_new, p.offset_s) for p in a] == \
        [(len(p.prompt), p.max_new, p.offset_s) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert any(not np.array_equal(p.prompt, q.prompt) for p, q in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips(name):
    mix = traffic.load_mix(name)
    plan = traffic.make_plan(mix, 3, 49155, 500)
    cls = mix["classes"][0]
    lens = [len(p.prompt) for p in plan]
    skip = mix.get("clients", 0)     # the closed loop's staggered start
    outs = [p.max_new for p in plan[skip:]]
    assert all(1 <= p.max_new <= cls["output"]["max"] for p in plan[:skip])
    assert min(lens) >= cls["prompt"]["min"]
    assert max(lens) <= cls["prompt"]["max"]
    assert min(outs) >= cls["output"]["min"]
    assert max(outs) <= cls["output"]["max"]
    assert all(0 <= p.prompt.min() and p.prompt.max() < 49155 for p in plan)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = traffic.load_mix(name)
    blk = mix["block"]
    skip = mix.get("clients", 0)     # the closed loop's staggered start
    one = traffic.make_plan(mix, 1, 100, 4 * blk)
    two = traffic.make_plan(mix, 99, 100, 4 * blk)
    for k in range(1, 4):
        a = one[k * blk:(k + 1) * blk]
        b = two[k * blk:(k + 1) * blk]
        assert k * blk >= skip
        assert Counter(len(p.prompt) for p in a) == \
            Counter(len(p.prompt) for p in b)
        assert Counter(p.max_new for p in a) == Counter(p.max_new for p in b)
    assert [len(p.prompt) for p in one] != [len(p.prompt) for p in two]


def test_open_loop_rate_and_gaps():
    mix = traffic.load_mix("chat-poisson")
    plan = traffic.make_plan(mix, 4, 100, 10 * mix["block"])
    t = np.array([p.offset_s for p in plan])
    assert np.all(np.diff(t) > 0)
    assert len(plan) / t[-1] == pytest.approx(mix["rate_per_s"], rel=0.02)
    # the gaps of a block are the same multiset for every seed
    blk = mix["block"]
    other = np.array([p.offset_s for p in traffic.make_plan(mix, 5, 100, blk)])
    assert sorted(np.diff(np.concatenate([[0.0], t[:blk]]))) == \
        pytest.approx(sorted(np.diff(np.concatenate([[0.0], other]))))


def test_bursts_keep_the_mean_rate():
    mix = dict(traffic.load_mix("chat-poisson"),
               bursts={"period_s": 10.0, "on_s": 2.0, "factor": 4.0})
    plan = traffic.make_plan(mix, 8, 100, 40 * mix["block"])
    t = np.array([p.offset_s for p in plan])
    assert len(t) / t[-1] == pytest.approx(mix["rate_per_s"], rel=0.03)
    on = np.mean((t % 10.0) < 2.0)
    # 2 s of every 10 at four times the rate carry 8 of every 10 s's load
    assert on == pytest.approx(0.8, abs=0.03)


def test_closed_loop_staggers_the_first_round():
    mix = traffic.load_mix("rag-closed32")
    plan = traffic.make_plan(mix, 6, 100, 128)
    first = [p.max_new for p in plan[:mix["clients"]]]
    assert len(set(first)) > mix["clients"] // 2
    # no length shared by as many as could fill one warmed admission and
    # come free in one tick
    assert max(Counter(first).values()) < 4
    assert np.mean(first) < np.mean([p.max_new for p in plan[64:128]])


@pytest.mark.parametrize("name", MIXES)
def test_stream_extends_the_plan(name):
    """A closed loop that runs past its plan draws the same requests that
    a longer plan would have held."""
    mix = traffic.load_mix(name)
    blk = mix["block"]
    short = traffic.make_plan(mix, 2 ** 32 + 3, 1000, blk)
    stream = traffic.iter_plan(mix, 2 ** 32 + 3, 1000)
    first = [next(stream) for _ in range(3 * blk)]
    assert [(len(p.prompt), p.max_new, p.offset_s) for p in short] == \
        [(len(p.prompt), p.max_new, p.offset_s) for p in first[:blk]]
    longer = traffic.make_plan(mix, 2 ** 32 + 3, 1000, 3 * blk)
    assert all(np.array_equal(p.prompt, q.prompt) and p.max_new == q.max_new
               and p.offset_s == q.offset_s for p, q in zip(first, longer))
