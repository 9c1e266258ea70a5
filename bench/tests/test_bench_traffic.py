"""The one traffic generator: deterministic per seed, inside each mix's
parameters, and the same work for every seed."""

from collections import Counter

import numpy as np
import pytest

from bench import traffic

MIXES = ["qwen1.5-4b.chat.r80", "internlm2-20b-l8.batch"]
SEED = 2**31 + 977  # seeds may pass 32 signed bits


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a, b = traffic.Traffic(mix, SEED, 1000), traffic.Traffic(mix, SEED, 1000)
    for i in range(300):
        x, y = a.spec(i), b.spec(i)
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)
    c = traffic.Traffic(mix, SEED + 1, 1000)
    assert any(not np.array_equal(a.spec(i).prompt, c.spec(i).prompt)
               for i in range(10))


@pytest.mark.parametrize("name", MIXES)
def test_requests_stay_inside_the_mix(name):
    mix = traffic.load_mix(name)
    gen = traffic.Traffic(mix, SEED, 1000)
    lengths = set(traffic.prompt_lengths(mix))
    for i in range(400):
        s = gen.spec(i)
        assert len(s.prompt) in lengths
        assert s.prompt.min() >= 0 and s.prompt.max() < 1000
        assert mix["output"]["min"] <= s.max_new
        assert len(s.prompt) + s.max_new <= mix["max_total"]
    if mix["loop"] == "open":
        dues = [gen.due(i) for i in range(1280)]
        assert all(b > a for a, b in zip(dues, dues[1:]))
        # 10 whole cycles: the mean gap is the rate's, to the stratification
        assert dues[-1] / 1280 == pytest.approx(1 / mix["rate_per_s"], rel=0.02)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = traffic.load_mix(name)
    k = mix["cycle"]

    def cycle(seed):
        g = traffic.Traffic(mix, seed, 1000)
        return Counter((len(g.spec(i).prompt), g.spec(i).max_new)
                       for i in range(k))

    assert cycle(1) == cycle(SEED)
    if mix["loop"] == "open":
        a, b = traffic.Traffic(mix, 1, 1000), traffic.Traffic(mix, SEED, 1000)
        assert a.due(k - 1) == pytest.approx(b.due(k - 1))


def test_chat_lengths_follow_the_published_shape():
    mix = traffic.load_mix("qwen1.5-4b.chat.r80")
    assert traffic.prompt_lengths(mix) == [64, 128, 192, 256, 320, 384]
    g = traffic.Traffic(mix, 0, 1000)
    outs = sorted(g.spec(i).max_new for i in range(mix["cycle"]))
    assert outs[0] == 32 and 60 <= outs[len(outs) // 2] <= 70


def test_quantiles_of_each_distribution():
    assert traffic.quantile({"dist": "choice", "values": [1, 2, 3]}, 0.99) == 3
    assert traffic.quantile({"dist": "uniform_int", "min": 16, "max": 64}, 0.0) == 16
    assert traffic.quantile({"dist": "uniform_int", "min": 16, "max": 64}, 0.999) == 64
    ln = {"dist": "lognormal", "median": 160, "sigma": 0.6}
    assert traffic.quantile(ln, 0.5) == 160
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
