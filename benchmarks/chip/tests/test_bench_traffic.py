"""The traffic generator: deterministic per seed, and the stated
distributions."""
import math
from statistics import NormalDist

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import spec
from chipbench import traffic as tg

MIXES = ["chat-poisson", "longprompt-closed"]


def _specs(mix, seed, n=400):
    tr = spec._json(spec.traffic_file(mix))
    return tg.draw_specs(tr, n, seed, vocab=50304, max_seq=2048), tr


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, _ = _specs(mix, 2 ** 31 + 17)
    b, _ = _specs(mix, 2 ** 31 + 17)
    c, _ = _specs(mix, 5)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_sizes_in_the_same_order(mix):
    a, _ = _specs(mix, 1)
    b, _ = _specs(mix, 2)
    assert [len(s.prompt) for s in a] == [len(s.prompt) for s in b]
    assert [s.max_new for s in a] == [s.max_new for s in b]
    assert [s.greedy for s in a] == [s.greedy for s in b]
    assert [s.prompt for s in a] != [s.prompt for s in b]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_stated_distribution(mix):
    specs, tr = _specs(mix, 9, n=2000)
    for key, vals in (("prompt", [len(s.prompt) for s in specs]),
                      ("output", [s.max_new for s in specs])):
        d = tr[key]
        vals = np.asarray(vals)
        cap = tr.get("max_total", 2048)
        if key == "output":
            # outputs are cut so prompt + output fits the context
            prompts = np.asarray([len(s.prompt) for s in specs])
            assert np.all(vals + prompts <= min(cap, 2047))
            continue
        assert vals.min() >= d["min"] and vals.max() <= d["max"]
        if d["dist"] == "lognormal":
            assert abs(np.median(vals) - d["median"]) <= 1
            z = NormalDist().inv_cdf(0.75)
            q3 = d["median"] * math.exp(d["sigma"] * z)
            assert abs(np.percentile(vals, 75) - q3) / q3 < 0.01
        else:
            mid = (d["min"] + d["max"]) / 2
            assert abs(vals.mean() - mid) < 0.01 * mid


def test_chat_mixes_greedy_requests_in():
    specs, tr = _specs("chat-poisson", 3)
    greedy = [s for s in specs if s.greedy]
    assert len(greedy) == len(specs) // tr["greedy_every"]
    assert all(s.temperature == 0.0 for s in greedy)
    assert all(s.temperature == tr["sampling"]["temperature"]
               for s in specs if not s.greedy)


def test_open_schedule_rate_and_poisson_gaps():
    tr = spec._json(spec.traffic_file("chat-poisson"))
    sched = tg.open_schedule(tr, rate=5.0, span_s=400.0, seed=4,
                             vocab=1000, max_seq=2048)
    dues = np.asarray([s.due for s in sched])
    assert len(sched) == 2000
    assert np.all(np.diff(dues) >= 0) and dues[-1] == pytest.approx(400.0)
    gaps = np.diff(np.concatenate([[0.0], dues]))
    # exponential: the coefficient of variation is 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    other = tg.open_schedule(tr, 5.0, 400.0, 5, 1000, 2048)
    assert [s.due for s in other] == [s.due for s in sched]


def test_rate_from_knee():
    tr = spec._json(spec.traffic_file("chat-poisson"))
    cfg = spec._json(spec.config_file("olmo-1b"))
    assert tg.rate_of(tr, cfg) == pytest.approx(
        tr["load"] * cfg["knee_rps"]["chat"])
