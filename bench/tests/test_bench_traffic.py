"""The traffic generator: the same schedule for the same seed, the same
work for every seed in a uniformly random order, and lengths that follow
each traffic file."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

FILES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 977


def load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_same_seed_same_schedule(path):
    t = load(path)
    a = traffic.schedule(t, BIG_SEED, 30.0, 1000)
    b = traffic.schedule(t, BIG_SEED, 30.0, 1000)
    assert [(x.due, x.max_new_tokens, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new_tokens, x.prompt.tolist()) for x in b]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_every_seed_same_work_other_order(path):
    t = load(path)
    a = traffic.schedule(t, 1, 30.0, 1000)
    b = traffic.schedule(t, BIG_SEED, 30.0, 1000)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    if t["loop"] == "open" and t["arrivals"]["process"] == "poisson":
        ga, gb = np.diff([0.0] + [x.due for x in a]), \
            np.diff([0.0] + [x.due for x in b])
        np.testing.assert_allclose(np.sort(ga), np.sort(gb))


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_lengths_follow_the_file(path):
    t = load(path)
    items = traffic.schedule(t, 7, 60.0, 1000)
    for key, got in (("prompt", [len(x.prompt) for x in items]),
                     ("output", [x.max_new_tokens for x in items])):
        spec = t[key]
        got = np.asarray(got)
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        if spec["dist"] == "lognormal":
            assert abs(np.median(got) / spec["median"] - 1) < 0.03
            inner = got[(got > spec["min"]) & (got < spec["max"])]
            sigma = np.std(np.log(inner))
            assert 0.6 * spec["sigma"] < sigma <= spec["sigma"] * 1.02
        elif spec["dist"] == "loguniform":
            mid = np.exp((np.log(spec["min"]) + np.log(spec["max"] + 1)) / 2)
            assert abs(np.median(got) / mid - 1) < 0.03
        else:
            assert abs(np.mean(got) - (spec["min"] + spec["max"]) / 2) < 1.0
    assert all(0 <= int(x.prompt.max()) < 1000 for x in items)


def test_order_lets_long_prompts_and_short_gaps_bunch():
    """Any order is as likely as another: neighbours in the top eighth of
    the prompts come as often as independent draws give them, and runs
    of three or more occur."""
    t = {"loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 5.0},
         "prompt": {"dist": "uniform", "min": 1, "max": 100000},
         "output": {"dist": "uniform", "min": 1, "max": 4}}
    pairs, longest = [], 0
    for seed in range(60):
        lens = np.array([len(x.prompt) for x in
                         traffic.schedule(t, BIG_SEED + seed, 40.0, 10)])
        top = lens >= np.quantile(lens, 7 / 8)
        pairs.append(np.sum(top[1:] & top[:-1]))
        run = 0
        for hit in top:
            run = run + 1 if hit else 0
            longest = max(longest, run)
    n, k = len(top), int(top.sum())
    expected = k * (k - 1) / n
    assert abs(np.mean(pairs) / expected - 1) < 0.25
    assert longest >= 3


def test_poisson_rate_and_window_cover():
    t = {"loop": "open", "arrivals": {"process": "poisson", "rate_per_s": 5.0},
         "prompt": {"dist": "uniform", "min": 1, "max": 4},
         "output": {"dist": "uniform", "min": 1, "max": 4}}
    items = traffic.schedule(t, 3, 40.0, 10)
    due = np.array([x.due for x in items])
    assert np.all(np.diff(due) >= 0)
    assert due[-1] > 40.0
    assert abs(np.sum(due < 40.0) / 40.0 - 5.0) < 1.0


def test_bursty_rate_swings_between_trough_and_peak():
    t = {"loop": "open",
         "arrivals": {"process": "bursty", "rate_per_s": 2.0, "burst": 4.0,
                      "period_s": 10.0},
         "prompt": {"dist": "uniform", "min": 1, "max": 4},
         "output": {"dist": "uniform", "min": 1, "max": 4}}
    due = np.array([x.due for x in traffic.schedule(t, 5, 200.0, 10)])
    assert np.all(np.diff(due) >= 0)
    phase = (due % 10.0)
    # the sine peaks at 2.5 s into each period and bottoms at 7.5 s
    near_peak = np.sum((phase > 1.5) & (phase < 3.5))
    near_trough = np.sum((phase > 6.5) & (phase < 8.5))
    assert near_peak > 2.5 * near_trough
    mean_rate = np.sum(due < 200.0) / 200.0
    assert abs(mean_rate - 2.0 * 2.5) < 0.5


def test_closed_loop_list_and_bad_input():
    t = {"loop": "closed", "clients": 3,
         "prompt": {"dist": "loguniform", "min": 10, "max": 20},
         "output": {"dist": "uniform", "min": 1, "max": 4}}
    items = traffic.schedule(t, 1, 10.0, 50)
    assert len(items) == 3 * traffic.CLOSED_PER_CLIENT
    assert all(x.due == 0.0 for x in items)
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf", "min": 1, "max": 2}, 4)
    with pytest.raises(ValueError):
        traffic.bursty_times(np.array([1.0]), 1.0, 0.5, 10.0)
