"""The traffic generator: seeded, Poisson, clipped, streams apart."""
from __future__ import annotations

import chipbench_tiny as T  # noqa: F401
import numpy as np
import pytest

from chipbench import traffic, weights

CODE = {"arrivals": "poisson",
        "prompt": {"law": "lognormal", "median": 1500, "sigma": 0.789,
                   "min": 16, "max": 7936},
        "output": {"law": "lognormal", "median": 13, "sigma": 1.239,
                   "min": 1, "max": 256}}
BIG = 2 ** 33 + 12345


def _key(jobs):
    return [(j.req_id, j.due, len(j.prompt), j.max_new_tokens, j.prompt[:3])
            for j in jobs]


def test_same_seed_same_requests():
    a, _ = traffic.open_loop(CODE, 3.0, [10, 60], 49152, BIG)
    b, _ = traffic.open_loop(CODE, 3.0, [10, 60], 49152, BIG)
    c, _ = traffic.open_loop(CODE, 3.0, [10, 60], 49152, BIG + 1)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_lengths_clip_and_stratify():
    """Each segment holds the quantiles of the length laws, clipped, in a
    drawn order; every seed gets the same schedule (due times and sizes in
    one order, so the same work) and tokens of its own."""
    jobs, starts = traffic.open_loop(CODE, 4.0, [10, 100], 49152, 7)
    other, _ = traffic.open_loop(CODE, 4.0, [10, 100], 49152, 8)
    assert starts == [0.0, 10.0]
    assert [j.req_id for j in jobs] == list(range(440))
    win = [j for j in jobs if j.due >= 10]
    win2 = [j for j in other if j.due >= 10]
    assert len(win) == len(win2) == 400
    p = [len(j.prompt) for j in win]
    assert p == [len(j.prompt) for j in win2]
    assert p != sorted(p)
    assert [j.due for j in win] == [j.due for j in win2]
    assert [j.max_new_tokens for j in win] \
        == [j.max_new_tokens for j in win2]
    assert all(a.prompt != b.prompt for a, b in zip(win, win2))
    # the warm-up segment has a schedule of its own
    assert [len(j.prompt) for j in jobs[:40]] != p[:40]
    assert min(p) >= 16 and max(p) == 7936
    assert abs(np.median(p) - 1500) <= 16
    o = [j.max_new_tokens for j in win]
    assert min(o) == 1 and max(o) == 256 and abs(np.median(o) - 13) <= 1
    assert all(0 <= t < 49152 for j in jobs for t in j.prompt)


def test_arrivals_are_poisson():
    """Due times are sorted uniforms: the gaps of a Poisson process given
    its count, exponential with mean 1 / rate and no fixed structure."""
    jobs, _ = traffic.open_loop(CODE, 5.0, [2000], 10, 3)
    due = np.array([j.due for j in jobs])
    assert len(due) == 10000 and (np.diff(due) >= 0).all()
    assert 0 <= due.min() and due.max() < 2000
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.2, rel=0.02)
    # exponential: the standard deviation equals the mean, and a share
    # exp(-1) of the gaps is longer than the mean
    assert gaps.std() == pytest.approx(0.2, rel=0.05)
    assert (gaps > 0.2).mean() == pytest.approx(np.exp(-1), abs=0.02)
    # counts in windows of 10 s vary as a Poisson count's do (var = mean)
    counts = np.histogram(due, bins=200, range=(0, 2000))[0]
    assert counts.var() == pytest.approx(50, rel=0.3)


def test_streams_are_apart():
    """The arrival stream does not move when the length laws change."""
    longer = dict(CODE, prompt=dict(CODE["prompt"], median=3000))
    a, _ = traffic.open_loop(CODE, 3.0, [30], 49152, 11)
    b, _ = traffic.open_loop(longer, 3.0, [30], 49152, 11)
    assert [j.due for j in a] == [j.due for j in b]
    assert [j.max_new_tokens for j in a] == [j.max_new_tokens for j in b]
    with pytest.raises(ValueError):
        traffic.open_loop(dict(CODE, arrivals="closed"), 1.0, [4], 10, 0)


def test_percentile():
    assert traffic.percentile([5.0], 99) == 5.0
    assert traffic.percentile([1, 2, 3, 4], 50) == 2.5
    assert traffic.percentile(list(range(101)), 90) == 90.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


def test_weights_from_seed():
    import jax.numpy as jnp
    specs = [("a/w", (8, 4), "dense"), ("n", (4,), "norm"),
             ("e", (16, 4), "embed"), ("b", (4,), "bias")]
    make = weights.Builder(specs, 4)
    w1, w2, w3 = make(BIG), make(BIG), make(BIG + 1)
    for k in w1:
        assert w1[k].dtype == jnp.bfloat16
        assert (np.asarray(w1[k], np.float32)
                == np.asarray(w2[k], np.float32)).all()
    assert not (np.asarray(w1["a/w"], np.float32)
                == np.asarray(w3["a/w"], np.float32)).all()
    a = np.asarray(w1["a/w"], np.float32)
    assert np.abs(a).max() <= np.sqrt(3 / 8) + 1e-2
    assert np.abs(np.asarray(w1["n"], np.float32) - 1).max() <= 0.18
    tree = weights.to_tree({"a/w": w1["a/w"]},
                           {"a": {"w": np.zeros((8, 4))}})
    assert tree["a"]["w"] is w1["a/w"]
    with pytest.raises(KeyError):
        weights.to_tree({}, {"a": {"w": np.zeros((8, 4))}})
    with pytest.raises(ValueError):
        weights.to_tree({"a/w": w1["a/w"]}, {"a": {"w": np.zeros((4, 4))}})
