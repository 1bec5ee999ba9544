"""Seeded traffic: deterministic, the same work for every seed, and the
stated distributions."""

import numpy as np
import pytest

import tiny  # noqa: F401
import traffic as tf

MIX = {
    "loop": "open", "rate": 4.0, "pool": 2000,
    "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256, "max": 4096},
    "output": {"dist": "loguniform", "min": 32, "max": 256},
}


CLOSED = dict(MIX, loop="closed", clients=4)


def _phase(seed, n=50):
    return tf.Traffic(MIX, seed, vocab=1000).phase(n, 1)


def test_same_seed_same_requests():
    a, b = _phase(2**33 + 5), _phase(2**33 + 5)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.out_len == y.out_len and x.due == y.due
    c = tf.Traffic(CLOSED, 9, 1000)
    d = tf.Traffic(CLOSED, 9, 1000)
    assert all(np.array_equal(c.next().prompt, d.next().prompt) for _ in range(5))


def test_seeds_reorder_one_set_of_sizes_and_gaps():
    a, b = _phase(1, 24), _phase(2, 24)
    for key in ("out_len", "prompt"):
        va = sorted(len(r.prompt) if key == "prompt" else r.out_len for r in a)
        vb = sorted(len(r.prompt) if key == "prompt" else r.out_len for r in b)
        assert va == vb
    ga, gb = np.diff([r.due for r in a]), np.diff([r.due for r in b])
    assert not np.array_equal(ga, gb)
    assert a[0].due == 0.0
    # the window's arrivals all fall inside it: 24 = floor(rate x seconds)
    assert max(r.due for r in a) < 24 / MIX["rate"]
    c, d = tf.Traffic(CLOSED, 1, 1000), tf.Traffic(CLOSED, 2, 1000)
    assert sorted(c._prompts) == sorted(d._prompts)
    assert not np.array_equal(c._prompts, d._prompts)


def test_stated_distributions():
    n = 4000
    p = tf.lengths(MIX["prompt"], n)
    assert p.min() >= 256 and p.max() <= 4096
    assert np.median(p) == pytest.approx(1024, rel=0.01)
    o = tf.lengths(MIX["output"], n)
    # log-uniform: the geometric midpoint is the median
    assert np.median(o) == pytest.approx(np.sqrt(32 * 256), rel=0.02)
    g = np.diff([r.due for r in tf.Traffic(MIX, 3, 10).phase(n, 0)])
    assert np.mean(g) == pytest.approx(1 / MIX["rate"], rel=0.02)
    assert np.std(g) == pytest.approx(1 / MIX["rate"], rel=0.1)   # exponential


def test_closed_loop_has_no_arrival_times():
    t = tf.Traffic(CLOSED, 7, 10)
    assert all(t.next().due is None for _ in range(5))
    with pytest.raises(ValueError, match="unknown loop"):
        tf.Traffic(dict(MIX, loop="burst"), 0, 10)
