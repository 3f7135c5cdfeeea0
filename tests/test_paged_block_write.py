"""The packed step's paged KV write: whole-block read-modify-writes.

``blocks._write_chunk`` / ``blocks._write_decodes`` gather the blocks a
step touches, set the token rows in them and scatter whole blocks back,
into a single pool or into the stacked pool the layer scan carries.  They
must leave every block but the scratch block 0 exactly as the plain
per-token row scatter (``row_scatter`` below) leaves it, except that a
block given no valid token is never rewritten at all; and in the stack,
every other layer stays untouched.  Pools are stored as
``blocks.paged_block_shape`` says: as ``[bs, hd]`` pages at hd 128, folded
to 128-lane rows at hd 64."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import DictKey

from repro.kernels.ref import (paged_chunked_prefill_attention_ref,
                               paged_decode_attention_ref)
from repro.models import blocks as bk
from repro.models.packed import make_packed

BS, M, N, NK = 16, 8, 40, 2           # max_len = M * BS = 128


def row_scatter(pool, pk, kv):
    """One (physical block, offset) scatter per token row: positions past
    ``max_len`` go to block 0, every other row to its table's block."""
    C = pk.num_chunk
    if C:
        cpos = pk.positions()[:C]
        bidx = cpos // BS
        phys = jnp.where(bidx < M, pk.chunk_blocks[jnp.clip(bidx, 0, M - 1)],
                         0)
        pool = pool.at[phys, :, :, cpos % BS].set(kv[:C])
    if pk.num_decode:
        bidx = (pk.decode_ctx // BS)[:, None]
        phys = jnp.take_along_axis(pk.decode_blocks, bidx, axis=1)[:, 0]
        pool = pool.at[phys, :, :, pk.decode_ctx % BS].set(kv[C:])
    return pool


def block_write(ref, pk, kv):
    C = pk.num_chunk
    if C:
        ref = bk._write_chunk(ref, pk, kv[:C])
    if pk.num_decode:
        ref = bk._write_decodes(ref, pk, kv[C:])
    return ref


def _table(blocks):
    t = np.zeros((M,), np.int32)
    t[:len(blocks)] = blocks
    return t


# (chunk_start, C, chunk_len, the chunk's table): the table lists the
# blocks the engine allocated for [0, chunk_start + chunk_len), then
# scratch, unless a case says otherwise
CASES = {
    "block_boundary": (32, 32, 32, _table([3, 5, 7, 9])),
    "mid_block": (40, 32, 32, _table([3, 5, 7, 9, 11])),
    "short_chunk": (40, 32, 11, _table([3, 5, 7, 9])),
    "past_max_len": (112, 32, 16, _table([3, 5, 7, 9, 11, 13, 15, 17])),
    # a full chunk ending on a block boundary: the next block, shared with
    # another request's prefix, lies in the write's window but gets no
    # token
    "shared_next_block": (32, 32, 32, _table([3, 5, 7, 9, 21])),
    # a short chunk whose table already lists (reserved, shared) blocks
    # past its last valid token
    "shared_past_chunk_len": (32, 32, 8, _table([3, 5, 7, 21, 23])),
    "decode_only": (0, 0, 0, _table([])),
}
# decode lanes: (ctx, table); lanes past these are padding (ctx 0, a
# table of scratch only)
DECODES = [(5, _table([25])), (16, _table([27, 29])),
           (77, _table([31, 32, 33, 34, 35]))]
D = 5


def _packed(case, chunk=None, decodes=DECODES):
    start, C, n, table = chunk or CASES[case]
    ctx = np.zeros((D,), np.int32)
    tables = np.zeros((D, M), np.int32)
    for i, (c, t) in enumerate(decodes):
        ctx[i], tables[i] = c, t
    return make_packed(
        chunk_tokens=np.zeros((C,), np.int32), chunk_start=start,
        chunk_len=n, decode_tokens=np.zeros((D,), np.int32),
        decode_ctx=ctx, chunk_blocks=table, decode_blocks=tables)


def _kv_and_pool(pk, hd, key=0):
    """A pool as stored, and the step's token rows."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(key))
    pool = jax.random.normal(
        k0, (N,) + bk.paged_block_shape(NK, BS, hd), jnp.float32)
    kv = jax.random.normal(k1, (pk.num_tokens, NK, 2, hd), jnp.float32)
    return pool, kv


def _alone(pool):
    """A tail layer's own pool, as the layer gets it."""
    return bk.carry_in((DictKey(bk.POOL_KEY),), pool)


def _of_layer(stack, layer):
    """Layer ``layer`` of a stacked pool, as the layer scan hands it."""
    return bk.carry_in((DictKey(bk.POOL_KEY),), stack, jnp.int32(layer))


def _logical(pool, hd):
    return np.asarray(bk.unfold_blocks(pool, hd))


def _unwritten(pk):
    """Blocks of the chunk's table that hold no valid token this step."""
    start, n = int(pk.chunk_start), int(pk.chunk_len)
    return {int(b) for j, b in enumerate(np.asarray(pk.chunk_blocks))
            if b and not (start <= j * BS + BS - 1 and j * BS < start + n)}


def test_pages_fold_to_whole_lane_rows_below_128():
    """hd 64: a block is stored as 128-lane rows (the same bytes in
    row-major order); hd 128 and pages of fewer than 128 elements are
    stored as they are."""
    assert bk.paged_block_shape(NK, 16, 64) == (NK, 2, 8, 128)
    assert bk.paged_block_shape(NK, 16, 128) == (NK, 2, 16, 128)
    assert bk.paged_block_shape(NK, 2, 32) == (NK, 2, 2, 32)
    pool = jnp.arange(3 * NK * 2 * 16 * 64).reshape(3, NK, 2, 8, 128)
    np.testing.assert_array_equal(
        _logical(pool, 64), np.asarray(pool).reshape(3, NK, 2, 16, 64))


@pytest.mark.parametrize("hd", [128, 64])
@pytest.mark.parametrize("case", sorted(CASES))
def test_block_write_matches_row_scatter_but_block_0(case, hd):
    pk = _packed(case)
    pool, kv = _kv_and_pool(pk, hd)
    before = _logical(pool, hd)
    want = np.array(row_scatter(jnp.asarray(before), pk, kv))
    keep = sorted(_unwritten(pk))
    want[keep] = before[keep]               # never rewritten, not padded
    got = block_write(_alone(pool), pk, kv).pool
    assert got.shape == pool.shape
    got = _logical(got, hd)
    np.testing.assert_array_equal(got[1:], want[1:])
    for b in keep:                          # e.g. the shared block
        np.testing.assert_array_equal(got[b], before[b])


@pytest.mark.parametrize("hd", [128, 64])
@pytest.mark.parametrize("case", ["mid_block", "shared_past_chunk_len",
                                  "past_max_len"])
def test_block_write_into_the_stack_touches_one_layer(case, hd):
    """At its block offset in the stacked pool: the layer ends as the single
    pool does, every other layer (block 0 included) as it was."""
    pk = _packed(case)
    pool, kv = _kv_and_pool(pk, hd)
    stack = jnp.stack([pool + 1.0, pool, pool - 1.0])
    ref = block_write(_of_layer(stack, 1), pk, kv)
    got = np.asarray(bk.carry_out(stack, ref, 1))
    alone = np.asarray(block_write(_alone(pool), pk, kv).pool)
    np.testing.assert_array_equal(got[1, 1:], alone[1:])
    np.testing.assert_array_equal(got[0], np.asarray(stack[0]))
    np.testing.assert_array_equal(got[2], np.asarray(stack[2]))


@pytest.mark.parametrize("hd", [128, 64])
def test_stacked_rows_gather_the_layers_blocks(hd):
    """``LayerPool.stored`` gathers the layer's blocks in the stack as
    stored, and ``blocks`` unfolds them into the layer's own pool's
    ``[bs, hd]`` pages; ``whole`` is that pool."""
    pk = _packed("mid_block")
    pool, _ = _kv_and_pool(pk, hd)
    stack = jnp.stack([pool * 2.0, pool])
    ref = _of_layer(stack, 1)
    logical = jnp.asarray(_logical(pool, hd))
    np.testing.assert_array_equal(np.asarray(ref.stored(pk.decode_blocks)),
                                  np.asarray(pool[pk.decode_blocks]))
    np.testing.assert_array_equal(np.asarray(ref.blocks(pk.decode_blocks, hd)),
                                  np.asarray(logical[pk.decode_blocks]))
    np.testing.assert_array_equal(np.asarray(ref.whole(hd)),
                                  np.asarray(logical))


# ------------------------------------------------------- the tiled read
NQ = 4
# (chunk_start, C, chunk_len, the chunk's table; decode lanes (ctx,
# table), the rest padding).  The "cow_shared" decodes share the chunk's
# prefix blocks 3 and 5, as a forked prefix leaves them
READS = {
    "ragged": ((40, 32, 32, _table([3, 5, 7, 9, 11])), DECODES),
    # 2-block tiles: the chunk's keys end on one, the longest decode's
    # last key opens the next
    "tile_boundary": ((0, 32, 32, _table([3, 5])),
                      [(32, _table([25, 27, 29])), (31, _table([31, 33]))]),
    "max_len": ((96, 32, 32, _table([3, 5, 7, 9, 11, 13, 15, 17])),
                [(127, _table([19, 21, 23, 25, 27, 29, 31, 33])),
                 (32, _table([35, 36, 37]))]),
    "cow_shared": ((32, 32, 8, _table([3, 5, 7])),
                   [(40, _table([3, 5, 21])), (33, _table([3, 5, 23]))]),
    "empty_chunk": ((0, 32, 0, _table([])), []),
}


def _read_case(case, hd, dtype):
    """The case's step, a pool as stored and its q, k, v rows."""
    pk = _packed(case, *READS[case])
    C = pk.num_chunk
    ks = jax.random.split(jax.random.PRNGKey(sorted(READS).index(case)), 4)
    pool = jax.random.normal(
        ks[0], (N,) + bk.paged_block_shape(NK, BS, hd)).astype(dtype)
    q, k, v = (jax.random.normal(kk, (C + D, h, hd)).astype(dtype)
               for kk, h in zip(ks[1:], (NQ, NK, NK)))
    return pk, pool, q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile", [2, 3, None])
@pytest.mark.parametrize("hd", [128, 64])
@pytest.mark.parametrize("case", sorted(READS))
def test_tiled_read_matches_the_paged_oracles(case, hd, tile, dtype,
                                              monkeypatch):
    """The xla branch of the packed step, its key loop bounded by the
    step's longest live context, reads what ``kernels/ref.py``'s paged
    oracles read from the pool it wrote: every decode lane (padding lanes
    at context 0 included) and every valid chunk row.  Tiles of 2 and 3
    blocks (3 does not divide the table) walk 1-4 steps; the module's
    tile spans the whole table.  The layer sits at its offset in a
    stacked pool."""
    if tile is not None:
        monkeypatch.setattr(bk, "KV_TILE", tile)
    pk, pool, q, k, v = _read_case(case, hd, dtype)
    C = pk.num_chunk
    stack = jnp.stack([pool * 2, pool])
    cfg = type("Cfg", (), {"head_dim": hd})
    out, cache = bk._attn_packed_paged(cfg, None, q, k, v, pk.positions(),
                                       {bk.POOL_KEY: _of_layer(stack, 1)},
                                       pk)
    written = jnp.asarray(_logical(
        bk.carry_out(stack, cache[bk.POOL_KEY], 1)[1], hd))
    want_c = paged_chunked_prefill_attention_ref(
        q[:C], written, pk.chunk_blocks, pk.chunk_start)
    want_d = paged_decode_attention_ref(
        q[C:], written, pk.decode_blocks, pk.decode_ctx)
    # an empty chunk's rows all lie in the first tile, which every read
    # walks: they read what the oracle reads too
    rows = int(pk.chunk_len) or C
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got = np.asarray(out, np.float32)
    np.testing.assert_allclose(got[:rows], np.asarray(want_c[:rows],
                                                      np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(got[C:], np.asarray(want_d, np.float32),
                               rtol=tol, atol=tol)
