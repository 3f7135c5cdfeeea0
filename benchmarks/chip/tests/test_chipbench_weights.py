"""Weights from the seed: the named laws draw the bits they always drew,
a reference can state a uniform law of its own, and a leaf too large for
the 32-bit counter is drawn slice by slice."""
from __future__ import annotations

import hashlib

import chipbench_tiny as T  # noqa: F401  (puts the benchmark on sys.path)
import numpy as np
import pytest

from chipbench import weights

SPECS = [("embed", (256, 64), "embed"), ("final_norm", (64,), "norm"),
         ("groups/0/mixer/wq", (2, 64, 64), "dense"),
         ("groups/0/mixer/bq", (2, 64), "bias")]
# sha256 of the bf16 bits, as the generator drew them before it took
# other laws and sliced leaves
PINNED = {
    (7, "embed"): "b1701781ae0be616",
    (7, "final_norm"): "ea478969aa5627d2",
    (7, "groups/0/mixer/wq"): "e516e94c4acc629d",
    (7, "groups/0/mixer/bq"): "f914ea24ac096f29",
    (2 ** 33 + 12345, "embed"): "57c6946b0061ec4f",
    (2 ** 33 + 12345, "final_norm"): "11eb50645878d5da",
    (2 ** 33 + 12345, "groups/0/mixer/wq"): "3781cebe915725a2",
    (2 ** 33 + 12345, "groups/0/mixer/bq"): "cbb726189dc23830",
}


def _hash(a):
    return hashlib.sha256(np.asarray(a).view(np.uint16).tobytes()) \
        .hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted({s for s, _ in PINNED}))
def test_named_laws_draw_the_bits_they_drew(seed):
    w = weights.Builder(SPECS, 64)(seed)
    for name, _, _ in SPECS:
        assert _hash(w[name]) == PINNED[seed, name], name


def test_uniform_law_of_a_reference():
    w = weights.Builder([("a_log", (4096,), ("uniform", 2.0, 0.5)),
                         ("d", (4096,), ("uniform", 0.0, 0.1))], 64)(11)
    a = np.asarray(w["a_log"], np.float32)
    assert abs(a.mean() - 2.0) < 0.03 and abs(a.std() - 0.5) < 0.02
    assert np.abs(a - 2.0).max() <= 0.5 * np.sqrt(3) + 1e-2
    # the same unit values as the bias law (std 0.1), with its own salt
    b = weights.Builder([("d", (4096,), "bias")], 64)(11)["d"]
    assert (np.asarray(w["d"], np.float32)
            == np.asarray(b, np.float32)).all()
    with pytest.raises(ValueError):
        weights.Builder([("x", (4,), ("normal", 0.0, 1.0))], 64)(11)


def test_large_leaf_is_drawn_slice_by_slice(monkeypatch):
    """With the counter's limit at 64 elements, a [3, 8, 16] leaf is
    drawn as 24 slices of 16, slice (i, j) hashed as '<name>/i/j'; a leaf
    under the limit is drawn whole, as before."""
    monkeypatch.setattr(weights, "COUNTER_LIMIT", 64)
    big = weights.Builder([("w", (3, 8, 16), "bias"),
                           ("small", (4, 8), "dense")], 64)(5)
    monkeypatch.setattr(weights, "COUNTER_LIMIT", 2 ** 32)
    parts = weights.Builder([(f"w/{i}/{j}", (16,), "bias")
                             for i in range(3) for j in range(8)], 64)(5)
    want = np.stack([np.stack([np.asarray(parts[f"w/{i}/{j}"], np.float32)
                               for j in range(8)]) for i in range(3)])
    assert (np.asarray(big["w"], np.float32) == want).all()
    whole = weights.Builder([("small", (4, 8), "dense")], 64)(5)["small"]
    assert (np.asarray(big["small"], np.float32)
            == np.asarray(whole, np.float32)).all()
    monkeypatch.setattr(weights, "COUNTER_LIMIT", 8)
    with pytest.raises(ValueError):
        weights.Builder([("v", (16,), "bias")], 64)(5)
