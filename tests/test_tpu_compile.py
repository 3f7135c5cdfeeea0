"""Compile-only checks of the paged Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip would
refuse (DMA slices not aligned to the tiled minor axes, too much VMEM).
Interpret-mode tests cannot see either.  The shapes are Granite-8B's
(32 query and 8 KV heads of 128, bf16) at the serving geometry of
``chip_smoke.py``: 16 slots (15 decode lanes) of up to 4,096 tokens and
256-token prefill chunks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_chunked_prefill_attention as pcpa
from repro.kernels import paged_decode_attention as pda
from repro.models import blocks as bk

NQ, NK, HD = 32, 8, 128
DTYPE = jnp.bfloat16
DECODE_LANES, CHUNK, MAX_LEN = 15, 256, 4096
# default tiles (env defaults) and one quad-buffered, two-page variant
TILES = [pytest.param({}, id="default"),
         pytest.param(dict(kv_pages=2, n_buffers=4), id="pages2-buf4")]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from a persistent
    # cache without one: keep them out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", saved)


def _pool_shape(bs):
    m = MAX_LEN // bs
    return (16 * m + 1, NK, 2, bs, HD), m


def _compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("bs", [16, 128])
def test_paged_decode_kernel_compiles(topo, bs, tiles):
    one = SingleDeviceSharding(topo.devices[0])
    pool, m = _pool_shape(bs)
    args = (jax.ShapeDtypeStruct((DECODE_LANES, NQ, HD), DTYPE, sharding=one),
            jax.ShapeDtypeStruct(pool, DTYPE, sharding=one),
            jax.ShapeDtypeStruct((DECODE_LANES, m), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((DECODE_LANES,), jnp.int32, sharding=one))
    _compiles(functools.partial(pda.paged_decode_attention, interpret=False,
                                **tiles), *args)


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("bs", [16, 128])
def test_paged_chunked_prefill_kernel_compiles(topo, bs, tiles):
    one = SingleDeviceSharding(topo.devices[0])
    pool, m = _pool_shape(bs)
    args = (jax.ShapeDtypeStruct((CHUNK, NQ, HD), DTYPE, sharding=one),
            jax.ShapeDtypeStruct(pool, DTYPE, sharding=one),
            jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one))
    _compiles(functools.partial(pcpa.paged_chunked_prefill_attention,
                                bq=128, interpret=False, **tiles), *args)


def test_shard_mapped_decode_kernel_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"))
    pool, m = _pool_shape(16)

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = (arg((DECODE_LANES, NQ, HD), DTYPE, P(None, "model", None)),
            arg(pool, DTYPE, P(None, "model", None, None, None)),
            arg((DECODE_LANES, m), jnp.int32, P()),
            arg((DECODE_LANES,), jnp.int32, P()))
    kernel = functools.partial(pda.paged_decode_attention, interpret=False)
    _compiles(bk._shard_map_heads(kernel, mesh, n_table_args=2), *args)


def test_named_scopes_change_no_tpu_program(topo, monkeypatch):
    """The packed step's named scopes change only metadata and names in
    what the TPU compiler makes of both step shapes (a tiny engine)."""
    from test_obs import assert_scopes_change_only_metadata
    assert_scopes_change_only_metadata(
        monkeypatch, SingleDeviceSharding(topo.devices[0]))
