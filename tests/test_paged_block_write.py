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

from repro.models import blocks as bk
from repro.models import common as cm
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


def _packed(case):
    start, C, n, table = CASES[case]
    ctx = np.zeros((D,), np.int32)
    tables = np.zeros((D, M), np.int32)
    for i, (c, t) in enumerate(DECODES):
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
    """``LayerPool.rows`` reads the layer's blocks in the stack, as
    ``gather_block_rows`` reads the layer's own pool; ``whole`` is that
    pool."""
    pk = _packed("mid_block")
    pool, _ = _kv_and_pool(pk, hd)
    stack = jnp.stack([pool * 2.0, pool])
    ref = _of_layer(stack, 1)
    logical = jnp.asarray(_logical(pool, hd))
    np.testing.assert_array_equal(
        np.asarray(ref.rows(pk.decode_blocks, hd)),
        np.asarray(cm.gather_block_rows(logical, pk.decode_blocks)))
    np.testing.assert_array_equal(np.asarray(ref.whole(hd)),
                                  np.asarray(logical))
