"""Paged KV path: kernels vs oracles, paged engine vs dense engine, and
online serving under pool pressure (preemption by recompute)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import cached_model
from repro.core import ChunkWork, DecodeWork, Engine, IterationPlan, \
    plan_chunks
from repro.kernels import ops, ref


# --------------------------------------------------------------------------
# kernel-level: paged Pallas kernels vs the pure-jnp oracles
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,M,bs,nq,nk,hd", [
    (3, 4, 32, 8, 2, 64),          # GQA, shuffled tables
    (2, 2, 128, 4, 4, 64),         # MHA, MXU-sized blocks
    (1, 8, 16, 14, 2, 64),         # qwen2 heads, small blocks
])
def test_paged_decode_attention(B, M, bs, nq, nk, hd, dtype):
    N = B * M + 1
    ks = jax.random.split(jax.random.PRNGKey(B * M + bs), 3)
    q = jax.random.normal(ks[0], (B, nq, hd), dtype)
    pool_k = jax.random.normal(ks[1], (N, bs, nk, hd), dtype)
    pool_v = jax.random.normal(ks[2], (N, bs, nk, hd), dtype)
    pool = ref.fuse_kv_pools(pool_k, pool_v)
    # non-trivial physical layout: blocks deliberately scattered
    perm = np.random.default_rng(0).permutation(np.arange(1, N))
    bt = perm[:B * M].reshape(B, M).astype(np.int32)
    ctx = jax.random.randint(jax.random.PRNGKey(9), (B,), 0, M * bs)
    out = ops.paged_decode_attention(q, pool, bt, ctx)
    want = ref.paged_decode_attention_ref(q, pool, bt, ctx)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("C,M,bs,nq,nk,hd,start", [
    (128, 3, 64, 4, 2, 64, 40),    # GQA, mid-prefix start
    (128, 2, 128, 8, 8, 64, 0),    # MHA, first chunk
    (64, 6, 32, 4, 1, 128, 100),   # MQA, small blocks (bq = C)
])
def test_paged_chunked_prefill_attention(C, M, bs, nq, nk, hd, start, dtype):
    N = M + 4
    ks = jax.random.split(jax.random.PRNGKey(C + M), 3)
    q = jax.random.normal(ks[0], (C, nq, hd), dtype)
    pool_k = jax.random.normal(ks[1], (N, bs, nk, hd), dtype)
    pool_v = jax.random.normal(ks[2], (N, bs, nk, hd), dtype)
    pool = ref.fuse_kv_pools(pool_k, pool_v)
    bt = np.random.default_rng(1).permutation(np.arange(1, N))[:M] \
        .astype(np.int32)
    out = ops.paged_chunked_prefill_attention(q, pool, bt, start)
    want = ref.paged_chunked_prefill_attention_ref(q, pool, bt, start)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# every (kv_pages, n_buffers) tiling — serial, double- and quad-buffered,
# incl. a page count that does NOT divide the table — must agree with the
# single-DMA-per-step pipeline bit-for-bit (same accumulation order: pages
# fold in logical order inside each step)
@pytest.mark.parametrize("kv_pages,n_buffers",
                         [(1, 1), (1, 4), (2, 2), (3, 2), (4, 4)])
def test_paged_decode_attention_buffering_variants(kv_pages, n_buffers):
    from repro.kernels import paged_decode_attention as pda
    B, M, bs, nq, nk, hd = 3, 5, 16, 4, 2, 64
    N = B * M + 1
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, nq, hd))
    pool = jax.random.normal(ks[1], (N, nk, 2, bs, hd))
    perm = np.random.default_rng(4).permutation(np.arange(1, N))
    bt = perm[:B * M].reshape(B, M).astype(np.int32)
    ctx = jnp.array([3, 37, 79], jnp.int32)
    want = ref.paged_decode_attention_ref(q, pool, bt, ctx)
    out = pda.paged_decode_attention(q, pool, bt, ctx, kv_pages=kv_pages,
                                     n_buffers=n_buffers, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_pages,n_buffers",
                         [(1, 1), (1, 4), (2, 2), (3, 2), (4, 4)])
def test_paged_chunked_prefill_attention_buffering_variants(kv_pages,
                                                            n_buffers):
    from repro.kernels import paged_chunked_prefill_attention as pcpa
    C, M, bs, nq, nk, hd, start = 32, 5, 16, 4, 2, 64, 41
    N = M + 3
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    q = jax.random.normal(ks[0], (C, nq, hd))
    pool = jax.random.normal(ks[1], (N, nk, 2, bs, hd))
    bt = np.random.default_rng(5).permutation(np.arange(1, N))[:M] \
        .astype(np.int32)
    want = ref.paged_chunked_prefill_attention_ref(q, pool, bt, start)
    out = pcpa.paged_chunked_prefill_attention(
        q, pool, bt, start, bq=16, kv_pages=kv_pages, n_buffers=n_buffers,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernels_ignore_scratch_padded_tail():
    """Table entries past the allocation point at the scratch block; its
    (garbage) contents must never affect the output."""
    B, M, bs, nq, nk, hd = 2, 4, 16, 4, 2, 64
    N = B * M + 1
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (B, nq, hd))
    pool = jax.random.normal(ks[1], (N, nk, 2, bs, hd))
    bt = np.arange(1, 1 + B * M).reshape(B, M).astype(np.int32)
    ctx = jnp.array([20, 40])
    bt_padded = bt.copy()
    bt_padded[0, 2:] = 0                       # ctx 20 fits in 2 blocks
    out_full = ops.paged_decode_attention(q, pool, bt, ctx)
    pool2 = pool.at[0].set(99.0)               # poison the scratch block
    out_pad = ops.paged_decode_attention(q, pool2, bt_padded, ctx)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_pad),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# engine-level: the paged cache must replay the dense engine exactly
# --------------------------------------------------------------------------
def _generate(eng, prompt, n_new, chunk):
    eng.add_request(0)
    out = []
    for c in plan_chunks(len(prompt), chunk):
        r = eng.execute(IterationPlan(chunk=ChunkWork(
            0, prompt[c.start:c.start + c.length], c.start, c.is_last)))
        if c.is_last:
            out.append(r[0])
    while len(out) < n_new:
        r = eng.execute(IterationPlan(decodes=[
            DecodeWork(0, out[-1], len(prompt) + len(out) - 1)]))
        out.append(r[0])
    eng.release(0)
    return out


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-0.5b"])
def test_paged_engine_matches_dense(arch):
    cfg, model, params = cached_model(arch)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 11).tolist()
    kw = dict(n_slots=2, max_len=64, chunk_size=4, decode_slots=2)
    want = _generate(Engine(cfg, params, **kw), prompt, 5, 4)
    paged = Engine(cfg, params, paged=True, block_size=16, **kw)
    got = _generate(paged, prompt, 5, 4)
    assert got == want
    # free-on-release drained the pool
    assert paged.block_manager.n_used == 0


def test_paged_engine_pallas_backend_matches_dense():
    """The block-table Pallas kernels (interpret mode here), selected via
    REPRO_PAGED_ATTN_BACKEND, replay the dense engine token-for-token."""
    cfg, model, params = cached_model("tinyllama-1.1b")
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 6).tolist()
    kw = dict(n_slots=1, max_len=64, chunk_size=8, decode_slots=1)
    want = _generate(Engine(cfg, params, **kw), prompt, 3, 8)
    os.environ["REPRO_PAGED_ATTN_BACKEND"] = "pallas"
    try:
        got = _generate(Engine(cfg, params, paged=True, block_size=16, **kw),
                        prompt, 3, 8)
    finally:
        del os.environ["REPRO_PAGED_ATTN_BACKEND"]
    assert got == want


def test_paged_slot_reuse_is_clean():
    """Freed blocks are recycled across requests; the newcomer must decode
    as if the pool were fresh (self-healing, no explicit wipe)."""
    cfg, model, params = cached_model("tinyllama-1.1b")
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, cfg.vocab_size, 9).tolist()
    p2 = rng.integers(0, cfg.vocab_size, 7).tolist()
    kw = dict(n_slots=1, max_len=64, chunk_size=16, decode_slots=1)
    want = _generate(Engine(cfg, params, **kw), p2, 3, 16)
    eng = Engine(cfg, params, paged=True, block_size=8, **kw)
    _generate(eng, p1, 2, 16)                   # dirty the pool
    assert _generate(eng, p2, 3, 16) == want


def test_paged_engine_exposes_pool_accounting():
    cfg, model, params = cached_model("tinyllama-1.1b")
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                 decode_slots=2, paged=True, block_size=16)
    # default pool: dense capacity minus the scratch row, plus 1 scratch blk
    assert eng.block_manager.n_blocks == 2 * (64 // 16) + 1
    eng.add_request(0)
    eng.execute(IterationPlan(chunk=ChunkWork(0, [1, 2, 3], 0, True)))
    assert eng.block_manager.n_used == 1        # 3 tokens -> one block
    eng.release(0)
    assert eng.block_manager.n_used == 0


@pytest.mark.parametrize("paged", [False, True])
def test_unaligned_final_chunk_padding_never_clobbers_context(paged):
    """A final chunk whose STATIC C-width window spills past max_len (an
    unaligned start the budget scheduler's chunk shrinking can produce)
    pads positions beyond the cache; those writes must be dropped (dense)
    or routed to the scratch block (paged) — never clamped onto live KV."""
    cfg, model, params = cached_model("tinyllama-1.1b")
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 62).tolist()

    def run(eng, bounds):
        eng.add_request(0)
        out = []
        for s, e in bounds:
            r = eng.execute(IterationPlan(chunk=ChunkWork(
                0, prompt[s:e], s, e == len(prompt))))
            if e == len(prompt):
                out.append(r[0])
        for _ in range(2):
            r = eng.execute(IterationPlan(decodes=[DecodeWork(
                0, out[-1], len(prompt) + len(out) - 1)]))
            out.append(r[0])
        eng.release(0)
        return out

    kw = dict(n_slots=1, max_len=64, chunk_size=32, decode_slots=1)
    want = run(Engine(cfg, params, **kw), [(0, 32), (32, 62)])  # no spill
    eng = Engine(cfg, params, paged=paged,
                 **(dict(block_size=16) if paged else {}), **kw)
    # last chunk: start=56, padded window covers 56..87 > max_len=64
    assert run(eng, [(0, 28), (28, 56), (56, 62)]) == want
