"""The one serving-traffic generator: what a mix's parameters promise."""

from __future__ import annotations

import collections

import numpy as np
import pytest

import bench
import traffic

SECONDS = 30.0


@pytest.fixture(params=["code-open-2.8rps", "code-repeat-4.0rps", "code-open-1.9rps"])
def mix(request):
    return bench.traffic_file(request.param)


def test_same_seed_same_requests_other_seed_other_prompts(mix):
    a = traffic.schedule(mix, SECONDS, bench.seeds(7)[1:3], 100352)
    b = traffic.schedule(mix, SECONDS, bench.seeds(7)[1:3], 100352)
    c = traffic.schedule(mix, SECONDS, bench.seeds(2**31 + 5)[1:3], 100352)
    assert [r.due for r in a] == [r.due for r in b] == [r.due for r in c]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    # every seed gets the same work: lengths and outputs in the same order
    assert [(r.prompt_len, r.gen) for r in a] == [(r.prompt_len, r.gen) for r in c]


def test_count_and_arrivals(mix):
    reqs = traffic.schedule(mix, SECONDS, bench.seeds(1)[1:3], 1000)
    n = round(mix["rate_per_s"] * SECONDS)
    assert len(reqs) == n
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < SECONDS
    # gaps are exponential quantiles at the mix's rate, scaled to the window
    gaps = np.sort(np.append(np.diff(due), SECONDS - due[-1]))
    want = np.sort(-np.log(1.0 - (np.arange(n) + 0.5) / n))
    np.testing.assert_allclose(gaps / gaps.mean(), want / want.mean(), rtol=1e-9)


def test_lengths_follow_the_mix(mix):
    reqs = traffic.schedule(mix, SECONDS, bench.seeds(1)[1:3], 1000)
    assert all(r.prompt_len in mix["prompt_lengths"] for r in reqs)
    assert all(mix["gen_min"] <= r.gen <= mix["gen_max"] for r in reqs)
    assert max(r.prompt_len + r.gen for r in reqs) <= mix["max_len"]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000 for r in reqs)
    if mix.get("repeat_share", 0) == 0:
        counts = collections.Counter(r.prompt_len for r in reqs)
        n = len(reqs)
        for length, w in zip(mix["prompt_lengths"], mix["prompt_weights"]):
            assert abs(counts[length] - n * w) < 1
        gens = np.array([r.gen for r in reqs])
        assert abs(gens.mean() - (mix["gen_min"] + mix["gen_max"]) / 2) < 1


def test_repeats_come_from_a_zipf_popular_set():
    mix = bench.traffic_file("code-repeat-4.0rps")
    reqs = traffic.schedule(mix, SECONDS, bench.seeds(3)[1:3], 100352)
    pop = [r for r in reqs if r.popular >= 0]
    assert len(pop) == round(len(reqs) * mix["repeat_share"])
    ranks = collections.Counter(r.popular for r in pop)
    assert max(ranks) < mix["popular"]
    assert ranks[0] == max(ranks.values())  # the most popular pair repeats most
    firsts = {}
    for r in pop:
        first = firsts.setdefault(r.popular, r)
        assert np.array_equal(first.prompt, r.prompt) and first.gen == r.gen
    uniques = [tuple(r.prompt[:8]) for r in reqs if r.popular < 0]
    assert len(set(uniques)) == len(uniques)


def test_warmup_covers_each_length_with_other_prompts(mix):
    words = bench.seeds(5)[1:3]
    warm = traffic.warmup_prompts(mix, SECONDS, words, 1000)
    reqs = traffic.schedule(mix, SECONDS, words, 1000)
    assert sorted(len(p) for p in warm) == sorted({r.prompt_len for r in reqs})
    assert not any(np.array_equal(p, r.prompt) for p in warm for r in reqs)


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 12345, 2**40])
def test_any_seed_gives_32_bit_words(seed):
    words = bench.seeds(seed)
    assert len(words) == 4 and all(0 <= w < 2**32 for w in words)
    assert words == bench.seeds(seed)
