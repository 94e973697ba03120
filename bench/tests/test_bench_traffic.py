"""The traffic generator: deterministic by seed, the same sizes for every
seed, and the stated length distributions."""
import json
import math
import statistics
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def sizes(draws):
    return sorted((len(d.prompt), d.max_new) for d in draws)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 32000, 2 ** 31 + 11, 30)
    b = traffic.generate(mix(name), 32000, 2 ** 31 + 11, 30)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.arrival) == (y.max_new, y.arrival)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_replays_the_same_schedule(name):
    a = traffic.generate(mix(name), 32000, 3, 30)
    b = traffic.generate(mix(name), 32000, 4_000_000_007, 30)
    assert [(len(d.prompt), d.max_new, d.arrival) for d in a] == \
        [(len(d.prompt), d.max_new, d.arrival) for d in b]
    # the seed draws the tokens
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_the_schedule_is_the_stated_quantiles_shuffled():
    t = mix("chat")
    d = traffic.generate(t, 32000, 1, 30)
    n = len(d)
    assert sorted(len(x.prompt) for x in d) == traffic.lengths(t["prompt"], n)
    assert sorted(x.max_new for x in d) == traffic.lengths(t["output"], n)
    assert [len(x.prompt) for x in d] != traffic.lengths(t["prompt"], n)
    q = np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)])
    arr = [x.arrival for x in d]
    assert np.isin(np.round(np.diff(arr), 6),
                   np.round(q * 30 / q.sum(), 6)).all()


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_to_the_stated_bounds(name):
    t = mix(name)
    draws = traffic.generate(t, 32000, 5, 30)
    p = [len(d.prompt) for d in draws]
    assert min(p) >= t["prompt"]["min"] and max(p) <= t["prompt"]["max"]
    o = [d.max_new for d in draws]
    assert min(o) >= t["output"]["min"] and max(o) <= t["output"]["max"]
    assert all(0 <= d.prompt.min() and d.prompt.max() < 32000
               for d in draws)


def test_lognormal_lengths_have_the_stated_median_and_spread():
    spec = {"dist": "lognormal", "median": 1024, "sigma": 0.7,
            "min": 1, "max": 10 ** 9}
    v = traffic.lengths(spec, 1001)
    assert statistics.median(v) == 1024
    logs = [math.log(x) for x in v]
    assert statistics.pstdev(logs) == pytest.approx(0.7, rel=0.02)


def test_uniform_lengths_span_the_range_evenly():
    v = traffic.lengths({"dist": "uniform", "min": 1024, "max": 3072}, 32)
    assert v[0] == 1056 and v[-1] == 3040
    assert statistics.fmean(v) == pytest.approx(2048, abs=1)


def test_poisson_arrivals_fill_the_window_at_the_rate():
    t = {"arrivals": {"kind": "poisson", "rate_per_s": 3.0},
         "prompt": {"dist": "uniform", "min": 8, "max": 8},
         "output": {"dist": "uniform", "min": 4, "max": 4}}
    d = traffic.generate(t, 100, 9, 40)
    arr = [x.arrival for x in d]
    assert len(d) == 120 and min(arr) == 0.0 and max(arr) < 40
    gaps = np.diff(sorted(arr))
    # exponential gaps: mean 1/rate, spread about as wide as the mean
    assert np.mean(gaps) == pytest.approx(40 / 120, rel=0.02)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.25)


def test_backlog_and_fill_are_due_at_zero():
    t = mix("docs")
    assert all(d.arrival == 0 for d in traffic.generate(t, 100, 1, 30))
    assert len(traffic.generate(t, 100, 1, 30)) == \
        t["arrivals"]["requests"]
