"""Seeded traffic: the same seed gives the same requests, every seed the
same set of sizes, and the sizes follow the stated distributions."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

BENCH = Path(__file__).resolve().parents[1]


def _mix(seed, **over):
    t = json.loads((BENCH / "traffic" / "reasoning.json").read_text())
    t.update(over)
    return T.make(t, seed, vocab=151936, max_seq=2304)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3, -9])
def test_same_seed_same_requests(seed):
    a, b = _mix(seed), _mix(seed)
    for x, y in zip(a.warm + a.requests, b.warm + b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens


def test_seeds_share_sizes_not_order_or_tokens():
    a, b = _mix(1), _mix(2)
    for size in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(size, a.requests)) == sorted(map(size, b.requests))
    assert [r.max_new_tokens for r in a.requests] != \
        [r.max_new_tokens for r in b.requests]
    assert not np.array_equal(a.requests[0].prompt, b.requests[0].prompt)
    assert sorted(len(r.prompt) for r in a.warm) == \
        sorted(len(r.prompt) for r in b.warm)


def test_reasoning_distribution():
    m = _mix(3)
    p = np.array([len(r.prompt) for r in m.requests])
    o = np.array([r.max_new_tokens for r in m.requests])
    assert set(p) == {128, 256} and (p == 128).sum() == (p == 256).sum()
    assert 256 <= o.min() and o.max() <= 2048
    assert abs(np.median(o) - 1024) <= 16
    assert all(len(r.prompt) + r.max_new_tokens - 1 <= 2304
               for r in m.warm + m.requests)
    assert m.clients == 8 and len(m.warm) == 8
    # every slot the window opens on has 1152 tokens or more still to come
    assert min(r.max_new_tokens for r in m.warm) >= 1152


def test_lognormal_median_and_clip():
    x = T.lengths({"lognormal": {"median": 512, "sigma": 0.8}}, 999,
                  T.rng_for(0))
    assert np.median(x) == 512          # the middle quantile is the median
    clipped = T.lengths({"lognormal": {"median": 64, "sigma": 1.0},
                         "min": 8, "max": 256}, 1000, T.rng_for(0))
    assert clipped.min() == 8 and clipped.max() == 256


def test_warm_start_holds_one_request_per_client():
    with pytest.raises(ValueError):
        _mix(1, clients=7)
