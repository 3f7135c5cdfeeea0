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
import dataclasses
import functools
import math
import os
import re

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


# an instruction's name, output shape and opcode in compiled HLO text
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$",
    re.M)
# what may output a pool-shaped array without moving the pool: values
# passed along, and the whole-block scatters into the stack
_PASS = ("parameter", "get-tuple-element", "tuple", "bitcast")


def pool_shaped_movers(hlo: str, n_blocks, block_dims, n_layers) -> list:
    """Instructions with an output of a pool's shape (a layer's pool
    ``[n_blocks, *block_dims]``, the stack of them, or the stack with its
    layer and block axes merged) that are neither a pass-through nor a
    scatter (a ``scatter`` or a fusion XLA made of one): copies,
    relayouts, slices and write-backs of whole pools."""
    tail = "," + ",".join(map(str, block_dims))
    heads = {f",{n_blocks}", f",{n_layers * n_blocks}"}
    out = []
    for name, shape, opcode, rest in _INSTR.findall(hlo):
        shape = "," + shape
        if (not any(shape.endswith(h + tail) for h in heads)
                or opcode in _PASS):
            continue
        op = re.search(r'op_name="([^"]*)"', rest)
        if opcode == "scatter" or (opcode == "fusion" and op
                                   and op.group(1).endswith("/scatter")):
            continue
        out.append(f"{name} {opcode} [{shape[1:]}]")
    return out


def _compiled_steps(topo, cfg, n_blocks, n_slots=8, max_len=1024):
    """Both packed steps (hybrid, decode-only) of an engine serving ``cfg``
    (``n_slots`` slots, one a chunk's and the rest decode lanes, of up to
    ``max_len`` tokens, blocks of 16, a pool of ``n_blocks``), compiled
    for one chip of the described v5e; and the pool's shape."""
    from repro.core.engine import DecodeWork, Engine
    from repro.models import build_model
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), DTYPE))
    eng = Engine(cfg, params, n_slots=n_slots, max_len=max_len,
                 chunk_size=CHUNK, decode_slots=n_slots - 1, dtype=DTYPE,
                 paged=True, block_size=16)
    cache = jax.eval_shape(lambda: model.init_cache(
        n_slots + 1, max_len, DTYPE, paged_blocks=n_blocks, block_size=16))
    eng.add_request(7)
    one = SingleDeviceSharding(topo.devices[0])
    steps = []
    for pad_chunk in (True, False):
        pk = eng._pack(None, [DecodeWork(7, 3, 5)], pad_chunk=pad_chunk)
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), (params, pk, cache, eng._key))
        steps.append(eng._step.lower(*args).compile())
    pool, = [a for a in jax.tree.leaves(cache) if a.ndim == 6]
    return steps, pool.shape


def _mistral_widths():
    """Mistral-7B's widths at 2 layers (``hd`` = 128)."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("tinyllama-1.1b"), n_layers=2, d_model=4096, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, vocab_size=32768,
        rope_theta=1e6)


def _qwen2_widths():
    """Qwen2-0.5B's widths at 2 layers (``hd`` = 64: a folded pool)."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               vocab_size=32768)


def test_layer_scan_moves_no_whole_pool(topo):
    """Both packed steps of an engine at Mistral-7B widths (2 layers, 513
    blocks of 16) compile, for a v5e, with no copy, slice or write-back
    of a layer's pool or of the stacked pool: the scan hands each layer
    the stack and writes whole blocks at the layer's block offset."""
    steps, shape = _compiled_steps(topo, _mistral_widths(), 513)
    assert shape == (2, 513, NK, 2, 16, HD)
    for step in steps:
        hlo = step.as_text()
        assert pool_shaped_movers(hlo, 513, (NK, 2, 16, HD), 2) == []
        assert re.search(r"= bf16\[1026,8,2,16,128\]\S* scatter\(", hlo)


def test_layer_scan_keeps_a_64_wide_pool_in_place(topo):
    """At Qwen2-0.5B widths (``hd`` = 64; 2 layers, a pool of 16,385
    blocks of 16) the pool is stored in 128-lane rows, and neither step
    copies it: no pool-shaped instruction but pass-throughs and scatters,
    and the step's temporaries stay under half the pool.  A ``[16, 64]``
    page would have the compiler lay the stack out with its block axis
    minor and copy all of it into a padded layout and back each step
    (temporaries about twice the pool)."""
    cfg = _qwen2_widths()
    assert cfg.head_dim == 64
    block = bk.paged_block_shape(cfg.n_kv_heads, 16, cfg.head_dim)
    assert block == (cfg.n_kv_heads, 2, 8, 128)
    steps, shape = _compiled_steps(topo, cfg, 16385)
    assert shape == (2, 16385) + block
    pool_bytes = math.prod(shape) * 2
    for step in steps:
        assert pool_shaped_movers(step.as_text(), 16385, block, 2) == []
        assert step.memory_analysis().temp_size_in_bytes < pool_bytes // 2


# an instruction's name, element type and output shape in HLO text
_TYPED = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]", re.M)


def full_table_shaped(hlo: str, lanes: int, width: int, bs: int) -> list:
    """Floating-point instructions shaped over ``lanes`` decode lanes'
    whole block tables ``width`` blocks wide: blocks ``[D, M, ...]`` or
    ``[D * M, ...]``, or token rows ``[D, M * bs, ...]`` (a leading unit
    axis aside)."""
    out = []
    for name, dtype, shape in _TYPED.findall(hlo):
        dims = [int(d) for d in shape.split(",") if d]
        if dims[:1] == [1]:
            dims = dims[1:]
        if dtype in ("bf16", "f32") and (
                (len(dims) >= 3 and dims[0] == lanes
                 and dims[1] in (width, width * bs))
                or (len(dims) >= 2 and dims[0] == lanes * width)):
            out.append(f"{name} {dtype}[{shape}]")
    return out


# both steps' temporaries (hybrid, decode-only) when the read gathered
# every decode lane's whole table and re-laid it out: the code before the
# tiled read, compiled by ``_compiled_steps`` at these geometries
_FULL_TABLE_TEMP = {"mistral": (475_048_960, 471_385_088),
                    "qwen2": (401_043_968, 400_201_216)}


@pytest.mark.parametrize("arch,widths,n_slots,max_len,n_blocks", [
    ("mistral", _mistral_widths, 8, 8192, 513),
    ("qwen2", _qwen2_widths, 64, 4096, 16385)])
def test_paged_read_spans_no_whole_table(topo, arch, widths, n_slots,
                                         max_len, n_blocks):
    """At the cells' geometries (Mistral: 7 decode lanes of up to 8,192
    tokens; Qwen2: 63 lanes of up to 4,096), neither packed step compiled
    for a v5e holds a float array over the decode lanes' whole tables:
    the read gathers one tile of table entries a loop step.  Each step's
    temporaries stay under a tenth of what the whole-table read took."""
    steps, _ = _compiled_steps(topo, widths(), n_blocks, n_slots, max_len)
    for step, before in zip(steps, _FULL_TABLE_TEMP[arch]):
        assert full_table_shaped(step.as_text(), n_slots - 1,
                                 max_len // 16, 16) == []
        assert step.memory_analysis().temp_size_in_bytes < before // 10
