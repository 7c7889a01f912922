"""The traffic generator: deterministic in the seed, true to its file."""
import collections

import numpy as np
import pytest

from bench import traffic
from smoke import ROOT

MIXES = ["chat", "noisy-neighbour"]


def load(name):
    return traffic.load_mix(ROOT / "bench" / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(load(name), seed=2**31 + 17, seconds=20,
                         vocab=1000, knee_rps=5.0)
    b = traffic.schedule(load(name), seed=2**31 + 17, seconds=20,
                         vocab=1000, knee_rps=5.0)
    assert [(r.due, r.tenant, r.prompt_len, r.out_len) for r in a] == \
        [(r.due, r.tenant, r.prompt_len, r.out_len) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_in_another_order(name):
    kw = dict(seconds=20, vocab=1000, knee_rps=5.0)
    a = traffic.schedule(load(name), seed=1, **kw)
    b = traffic.schedule(load(name), seed=2, **kw)
    for f in ("tenant", "prompt_len", "out_len"):
        assert sorted(getattr(r, f) for r in a) == \
            sorted(getattr(r, f) for r in b)
    assert [r.tenant for r in a] != [r.tenant for r in b]


def test_counts_rates_and_lengths_follow_the_file():
    mix = load("chat")
    s = mix["streams"][0]
    knee = 5.0
    rate = s["rate"]["knee_share"] * knee
    reqs = traffic.schedule(mix, seed=3, seconds=40, vocab=1000,
                            knee_rps=knee)
    warm = mix["warmup_s"]
    win = [r for r in reqs if warm <= r.due < warm + 40]
    assert len(win) == round(rate * 40)
    assert len(reqs) - len(win) == round(rate * warm)
    # due times lie in the span, in order
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    assert warm <= min(r.due for r in win) and max(r.due for r in win) < \
        warm + 40
    # prompt lengths in exact proportion to their probabilities
    got = collections.Counter(r.prompt_len for r in win)
    for v, p in zip(s["prompt_len"]["values"], s["prompt_len"]["probs"]):
        assert abs(got[v] - p * len(win)) <= 1
    # output lengths: log-normal quantiles, median and clip as stated
    outs = sorted(r.out_len for r in win)
    lo, hi = s["output_len"]["min"], s["output_len"]["max"]
    assert lo <= outs[0] and outs[-1] <= hi
    assert abs(outs[len(outs) // 2] - s["output_len"]["lognormal_median"]) \
        <= 3
    # Zipf(1.1) popularity over the tenants, in exact proportion
    pop = traffic.popularity(s)
    got_t = collections.Counter(r.tenant for r in win)
    for t, p in zip(s["tenants"], pop):
        assert abs(got_t[t] - p * len(win)) <= 1
    assert pop[0] / pop[1] == pytest.approx(2 ** 1.1)
    assert all(0 <= int(x) < 1000 for r in win for x in r.prompt)
    assert all(len(r.prompt) == r.prompt_len for r in win)


def test_noisy_neighbour_streams_and_weights():
    mix = load("noisy-neighbour")
    reqs = traffic.schedule(mix, seed=5, seconds=30, vocab=1000,
                            knee_rps=4.0)
    heavy = [r for r in reqs if r.tenant in (6, 7)]
    light = [r for r in reqs if r.tenant < 6]
    assert {(r.prompt_len, r.out_len) for r in heavy} == {(1536, 64)}
    for t in (6, 7):
        n = sum(1 for r in heavy if r.tenant == t)
        assert n == round(4.0 * 30) + round(4.0 * mix["warmup_s"])
    assert len(light) == round(0.6 * 4.0 * 30) + \
        round(0.6 * 4.0 * mix["warmup_s"])
    assert traffic.weights(mix)[7] == 2.0 and traffic.weights(mix)[6] == 1.0
    assert mix["latency_tenants"] == [0, 1, 2, 3, 4, 5]
    assert traffic.prompt_lengths(mix) == [128, 256, 512, 1024, 1536]


def test_arrivals_cluster_as_a_poisson_process():
    mix = {"weights": {"0": 1}, "latency_tenants": [0], "warmup_s": 0,
           "streams": [{"tenants": [0], "rate": {"rps": 5.0},
                        "prompt_len": {"values": [1], "probs": [1.0]},
                        "output_len": {"values": [1], "probs": [1.0]}}]}
    reqs = traffic.schedule(mix, seed=2**31 + 99, seconds=4000, vocab=2,
                            knee_rps=None)
    due = np.array([r.due for r in reqs])
    assert len(due) == 20000
    gaps = np.diff(due)
    # exponential gaps: the standard deviation equals the mean
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.03)
    # counts per second vary as a Poisson count does: variance = mean
    counts = np.bincount(due.astype(int), minlength=4000)
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.08)


def test_a_rate_against_the_knee_needs_the_knee():
    with pytest.raises(ValueError):
        traffic.schedule(load("chat"), seed=1, seconds=5, vocab=10,
                         knee_rps=None)


def test_another_seed_sends_at_other_times_in_another_order():
    mix = load("chat")
    kw = dict(seconds=51, vocab=100, knee_rps=1.75)
    a = traffic.schedule(mix, seed=11, **kw)
    b = traffic.schedule(mix, seed=12, **kw)
    assert len(a) == len(b)
    assert [r.due for r in a] != [r.due for r in b]
    assert [r.out_len for r in a] != [r.out_len for r in b]
    assert sorted(r.out_len for r in a) == sorted(r.out_len for r in b)
