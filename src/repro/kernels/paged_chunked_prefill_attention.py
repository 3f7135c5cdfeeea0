"""Pallas TPU kernel: chunked-prefill attention over a FUSED paged KV pool.

The SARATHI offset-causal chunk kernel (see
:mod:`repro.kernels.chunked_prefill_attention`) with the KV cache pooled
into ONE fused ``[n_blocks, nk, 2, block_size, hd]`` tensor and the
chunk's request addressed through its block table.  As in
:mod:`repro.kernels.paged_decode_attention`, the pool stays in ``ANY``
memory and the kernel drives its own DMAs: per grid step it copies
``kv_pages`` physical blocks' ``[2, bs, hd]`` K/V page pair for the
current head — one transfer each where the split-pool layout needed two,
cut along major axes only —
into an ``n_buffers``-slot VMEM ring, prefetched ahead of the flash
update so fetch overlaps compute.

Grid = (nq, C/bq, ceil(M / kv_pages)) with the KV/table axis innermost
("arbitrary" sequential semantics), flash accumulators in VMEM scratch.
Table entries past the request's allocation point at the scratch block;
their logical positions exceed ``start + C - 1`` so the causal mask hides
them, and tail pages past ``M`` clamp to the last entry for the same
reason.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (flash_finish, flash_init, flash_scores,
                               flash_update, paged_kv_pages,
                               paged_n_buffers, paged_q_block,
                               resolve_interpret)


def _kernel(start_ref, bt_ref, q_ref, pool_ref, o_ref, m_ref, l_ref,
            acc_ref, buf_ref, sem_ref, *, g: int, bq: int, bs: int,
            n_entries: int, kv_pages: int, n_buffers: int, n_steps: int,
            scale: float):
    h = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    def _copy(slot, step, p):
        t = jnp.minimum(step * kv_pages + p, n_entries - 1)
        return pltpu.make_async_copy(
            pool_ref.at[bt_ref[t], h // g],
            buf_ref.at[slot, p], sem_ref.at[slot, p])

    def _start(slot, step):
        for p in range(kv_pages):
            _copy(slot, step, p).start()

    @pl.when(j == 0)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)
        for t in range(min(n_buffers - 1, n_steps)):
            _start(t % n_buffers, t)

    ahead = j + n_buffers - 1
    @pl.when(ahead < n_steps)
    def _prefetch():
        _start(ahead % n_buffers, ahead)

    slot = j % n_buffers
    for p in range(kv_pages):
        _copy(slot, j, p).wait()

    start = start_ref[0]
    q = q_ref[0]                                    # [bq, hd]
    qpos = start + i * bq + \
        jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
    for p in range(kv_pages):
        k = buf_ref[slot, p, 0]                     # [bs, hd]
        v = buf_ref[slot, p, 1]
        s = flash_scores(q, k, scale)               # [bq, bs]
        kpos = (j * kv_pages + p) * bs + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        flash_update(m_ref, l_ref, acc_ref, s, kpos <= qpos, v)

    @pl.when(j == n_steps - 1)
    def _finish():
        o_ref[0] = flash_finish(m_ref, l_ref, acc_ref, o_ref.dtype)


def paged_chunked_prefill_attention(q, pool_kv, block_table, start, *,
                                    bq: Optional[int] = None,
                                    kv_pages: Optional[int] = None,
                                    n_buffers: Optional[int] = None,
                                    interpret: Optional[bool] = None):
    """q [C, nq, hd] — the prefill chunk's queries (positions start+i);
    pool_kv [n_blocks, nk, 2, block_size, hd] — the fused paged pool
    (the chunk's own KV already written through the table); block_table
    [M] int32 physical block ids (scratch-padded); start — scalar int32.
    Returns [C, nq, hd].  C must tile by bq; knobs default from
    :mod:`repro.kernels.ops`."""
    bq = paged_q_block() if bq is None else bq
    kv_pages = paged_kv_pages() if kv_pages is None else kv_pages
    n_buffers = paged_n_buffers() if n_buffers is None else n_buffers
    interpret = resolve_interpret() if interpret is None else interpret
    C, nq, hd = q.shape
    nk, bs = pool_kv.shape[1], pool_kv.shape[3]
    M = block_table.shape[0]
    kv_pages = max(1, min(kv_pages, M))
    bq = min(bq, C)
    if C % bq:
        raise ValueError(f"C={C} must tile by bq={bq}")
    g = nq // nk
    qh = jnp.moveaxis(q, 1, 0)                      # [nq, C, hd]
    n_steps = -(-M // kv_pages)
    grid = (nq, C // bq, n_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # start, block_table
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd),
                         lambda h, i, j, s_ref, bt_ref: (h, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool: kernel-side DMA
        ],
        out_specs=pl.BlockSpec((1, bq, hd),
                               lambda h, i, j, s_ref, bt_ref: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((n_buffers, kv_pages, 2, bs, hd), pool_kv.dtype),
            pltpu.SemaphoreType.DMA((n_buffers, kv_pages)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, g=g, bq=bq, bs=bs, n_entries=M,
                          kv_pages=kv_pages, n_buffers=n_buffers,
                          n_steps=n_steps, scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, C, hd), q.dtype),
        interpret=interpret,
        name="paged_chunked_prefill_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1),
      jnp.asarray(block_table, jnp.int32), qh, pool_kv)
    return jnp.moveaxis(out, 0, 1)
