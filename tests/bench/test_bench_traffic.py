"""The traffic generator: deterministic in the seed, the same work for every
seed, and within the bounds each mix states."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

BIG = 2**31 + 7  # run seeds may be wider than 32 signed bits


@pytest.fixture
def longdoc():
    return traffic.load_mix("longdoc-poisson")


def _sizes(reqs):
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new for r in reqs)


def test_same_seed_same_requests(longdoc):
    a = traffic.open_loop(longdoc, BIG, 51, 151936)
    b = traffic.open_loop(longdoc, BIG, 51, 151936)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_open_loop_bounds_and_count(longdoc):
    rate, seconds = longdoc["arrivals"]["rate_per_s"], 51
    reqs = traffic.open_loop(longdoc, BIG, seconds, 151936)
    assert len(reqs) == math.floor(rate * seconds)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < seconds
    p, o = longdoc["prompt_tokens"], longdoc["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 151936 for r in reqs)


@pytest.mark.parametrize("n", [14, 101, 1000])
def test_stratified_lognormal_median(n):
    spec = {"dist": "lognormal", "median": 3072, "sigma": 0.5, "min": 1024, "max": 7680}
    x = traffic.quantiles(spec, n)
    assert abs(np.median(x) - 3072) <= 3072 * 0.5 / n * 4 + 1
    assert x.min() >= 1024 and x.max() <= 7680


def test_gaps_sum_to_the_span():
    g = traffic.gaps({"process": "poisson"}, 50, 0.25)
    assert g.sum() == pytest.approx(200.0)
    assert (g > 0).all()


@pytest.mark.parametrize("order_seed", [5, None])
def test_fixed_order_ignores_the_seed(longdoc, order_seed):
    mix = dict(longdoc)
    if order_seed is not None:
        mix["order_seed"] = order_seed
    assert mix["order_seed"] is not None
    a = traffic.open_loop(mix, BIG, 51, 100)
    b = traffic.open_loop(mix, 3, 51, 100)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert _sizes(a) == _sizes(traffic.open_loop(mix, BIG + 1, 51, 100))


def test_closed_loop_mix_shape():
    mix = traffic.load_mix("ring-32k-closed")
    assert mix["kind"] == "closed_loop" and mix["layout"] == "zigzag"
    assert mix["sequence_tokens"] % (2 * 4) == 0
