"""Pure-jnp oracles for every kernel (tests assert_allclose against these)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import common as cm


def chunked_prefill_attention_ref(q, k, v, start):
    """q [C, nq, hd]; k, v [S, nk, hd]; start scalar."""
    C = q.shape[0]
    S = k.shape[0]
    q_pos = (jnp.asarray(start, jnp.int32)
             + jnp.arange(C, dtype=jnp.int32))[None]
    mask = cm.causal_cache_mask(q_pos, S)
    return cm.gqa_attention(q[None], k[None], v[None], mask)[0]


def decode_attention_ref(q, k, v, ctx):
    """q [B, nq, hd]; k, v [B, S, nk, hd]; ctx [B] (new token's position:
    keys at positions <= ctx are visible)."""
    mask = cm.causal_cache_mask(ctx[:, None].astype(jnp.int32), k.shape[1])
    return cm.gqa_attention(q[:, None], k, v, mask)[:, 0]


def gather_paged_rows(pool, block_tables):
    """Reconstruct dense cache rows from the fused paged pool: pool
    [N, nk, 2, bs, hd], block_tables [..., M] -> [..., M * bs, nk, 2, hd]
    (logical position order).  This is the oracle's view of block-table
    indirection — the paged kernels must behave as if attending these
    gathered rows."""
    return cm.gather_block_rows(pool, block_tables)


def fuse_kv_pools(pool_k, pool_v):
    """Split k/v pools [N, bs, nk, hd] -> one fused pool [N, nk, 2, bs, hd]
    (the layout the paged kernels consume)."""
    return jnp.moveaxis(cm.fuse_kv(pool_k, pool_v), 1, 3)


def paged_chunked_prefill_attention_ref(q, pool_kv, block_table, start):
    """q [C, nq, hd]; pool_kv [N, nk, 2, bs, hd] fused; block_table [M];
    start scalar."""
    rows_k, rows_v = cm.split_fused_kv(
        gather_paged_rows(pool_kv, block_table))
    return chunked_prefill_attention_ref(q, rows_k, rows_v, start)


def paged_decode_attention_ref(q, pool_kv, block_tables, ctx):
    """q [B, nq, hd]; pool_kv [N, nk, 2, bs, hd] fused; block_tables
    [B, M]; ctx [B]."""
    rows_k, rows_v = cm.split_fused_kv(
        gather_paged_rows(pool_kv, block_tables))
    return decode_attention_ref(q, rows_k, rows_v, ctx)
