"""Weights from the seed, made on the device in one jitted call.

Each weight is a counter-based hash of its element index, its name and the
seed, mapped to a uniform law of the weight's standard deviation, and cast
to the served dtype.  The same seed gives the same bits on any backend, so
the reference can rebuild the weights itself after the served program's
state is freed.

Laws (the third field of each of the reference module's ``weight_specs``):

* ``dense``: std 1/sqrt(fan_in), fan_in the second-to-last axis;
* ``embed``: std 1/sqrt(d_model), so tied logits are O(1);
* ``norm``: 1 + uniform of std 0.1;
* ``bias``: std 0.1;
* ``("uniform", mean, std)``: any other uniform law, such as that of a
  recurrence's decay or step bias.

The element counter has 32 bits: a weight of ``COUNTER_LIMIT`` elements or
more is drawn slice by slice along its leading axis, slice ``i`` hashed
under the name ``<name>/<i>``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_SQRT3 = math.sqrt(3.0)
COUNTER_LIMIT = 2 ** 32


def seed32(seed: int) -> np.uint32:
    """Fold a seed of any size into 32 bits."""
    s = int(seed)
    s = (s ^ (s >> 32) ^ (s >> 64)) & 0xFFFFFFFF
    return np.uint32(s)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _unit(seed, salt: int, shape):
    """Uniform values of mean 0 and std 1, [prod(shape)] hashed bits."""
    n = int(np.prod(shape))
    i = lax.iota(jnp.uint32, n)
    h = _fmix32(i * jnp.uint32(0x9E3779B1) + (seed ^ jnp.uint32(salt)))
    h = _fmix32(h ^ seed)
    u = (h >> 8).astype(jnp.float32) * (1.0 / (1 << 24))      # [0, 1)
    return ((2.0 * u - 1.0) * _SQRT3).reshape(shape)


def _law(kind, shape, d_model):
    """The map from unit values to the weight's law."""
    if kind == "dense":
        return lambda u: u / math.sqrt(shape[-2])
    if kind == "embed":
        return lambda u: u / math.sqrt(d_model)
    if kind == "norm":
        return lambda u: 1.0 + 0.1 * u
    if kind == "bias":
        return lambda u: 0.1 * u
    if isinstance(kind, (tuple, list)) and len(kind) == 3 \
            and kind[0] == "uniform":
        _, mean, std = kind
        return lambda u: mean + std * u
    raise ValueError(f"unknown weight law {kind!r}")


def _draw(seed, name, shape, law, dtype):
    if int(np.prod(shape)) < COUNTER_LIMIT:
        return law(_unit(seed, zlib.crc32(name.encode()), shape)).astype(
            dtype)
    if len(shape) < 2:
        raise ValueError(f"{name}: {shape} exceeds the 32-bit counter")
    return jnp.stack([_draw(seed, f"{name}/{i}", shape[1:], law, dtype)
                      for i in range(shape[0])])


def _leaf(seed, name, shape, kind, d_model, dtype):
    return _draw(seed, name, tuple(shape), _law(kind, shape, d_model), dtype)


class Builder:
    """Makes the weights of ``specs`` [(name, shape, kind)] from a seed, on
    the device; one compiled program serves every call."""

    def __init__(self, specs, d_model: int, dtype=jnp.bfloat16):
        def build(s):
            return {name: _leaf(s, name, shape, kind, d_model, dtype)
                    for name, shape, kind in specs}
        self._fn = jax.jit(build)

    def __call__(self, seed: int) -> dict:
        return jax.block_until_ready(self._fn(jnp.asarray(seed32(seed))))


def path_name(path) -> str:
    """'groups/0/mixer/wq' for a jax tree path."""
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def to_tree(flat: dict, template):
    """Place ``flat`` {path name: array} into the structure of
    ``template`` (the program's parameter tree of shapes); every leaf must
    be there with its shape, and no name may be left over."""
    used = set()

    def put(path, leaf):
        name = path_name(path)
        if name not in flat:
            raise KeyError(f"the program's parameter {name} {leaf.shape} has "
                           f"no weight in the configuration's reference")
        a = flat[name]
        if tuple(a.shape) != tuple(leaf.shape):
            raise ValueError(f"{name}: reference shape {a.shape}, program "
                             f"shape {leaf.shape}")
        used.add(name)
        return a

    tree = jax.tree_util.tree_map_with_path(put, template)
    extra = set(flat) - used
    if extra:
        raise KeyError(f"weights the program does not take: {sorted(extra)}")
    return tree
