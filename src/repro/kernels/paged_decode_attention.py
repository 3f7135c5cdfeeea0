"""Pallas TPU kernel: batched decode attention over a FUSED paged KV pool.

Same computation as :mod:`repro.kernels.decode_attention` — one new query
token per sequence attends its cached context — but the KV cache is ONE
pooled ``[n_blocks, nk, 2, block_size, hd]`` tensor (K at pair index 0,
V at 1) and each sequence's context lives in the physical blocks named by
its block table.

The pool stays in ``ANY`` memory (HBM) and the kernel issues its own
block-table DMAs: per grid step it fetches ``kv_pages`` physical blocks'
``[2, bs, hd]`` K/V page pair for the current head — ONE async copy per
page instead of the two a split-pool layout needs, slicing only the major
(block, head) axes so every copy moves whole ``[bs, hd]`` tiles — into an
``n_buffers``-slot VMEM scratch ring.  With ``n_buffers > 1`` the next
step's page fetches are started before the current step's flash-softmax
runs, so DMA overlaps compute (the split-pool predecessor let the implicit
BlockSpec pipeline serialize fetch against math).

Grid = (B, nk, ceil(M / kv_pages)), KV innermost, so the fp32 flash
accumulators persist in VMEM scratch across a sequence's sweep.  Table
entries past the sequence's allocation point at the scratch block
(physical block 0); their keys sit at logical positions beyond ``ctx`` and
are masked like any stale dense tail.  Tail pages past ``M`` clamp to the
last table entry — their logical positions are ``>= M * bs > ctx``, so
the mask hides whatever they fetched.
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
                               paged_n_buffers, resolve_interpret)


def _kernel(ctx_ref, bt_ref, q_ref, pool_ref, o_ref, m_ref, l_ref, acc_ref,
            buf_ref, sem_ref, *, bs: int, n_entries: int, kv_pages: int,
            n_buffers: int, n_steps: int, scale: float):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    def _copy(slot, step, p):
        # page p of `step`: physical block bt[b, t] (clamped tail pages
        # re-fetch the last entry; masked below), head h's K/V page pair
        t = jnp.minimum(step * kv_pages + p, n_entries - 1)
        return pltpu.make_async_copy(
            pool_ref.at[bt_ref[b, t], h],
            buf_ref.at[slot, p], sem_ref.at[slot, p])

    def _start(slot, step):
        for p in range(kv_pages):
            _copy(slot, step, p).start()

    @pl.when(j == 0)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)
        for t in range(min(n_buffers - 1, n_steps)):
            _start(t % n_buffers, t)

    # keep the ring full: the step landing in the slot the PREVIOUS
    # iteration just finished reading is safe to overwrite now (with
    # n_buffers == 1 this degenerates to fetching step j itself, serial)
    ahead = j + n_buffers - 1
    @pl.when(ahead < n_steps)
    def _prefetch():
        _start(ahead % n_buffers, ahead)

    slot = j % n_buffers
    for p in range(kv_pages):
        _copy(slot, j, p).wait()

    ctx = ctx_ref[b]
    q = q_ref[0, 0]                                 # [g, hd]
    for p in range(kv_pages):
        k = buf_ref[slot, p, 0]                     # [bs, hd]
        v = buf_ref[slot, p, 1]
        s = flash_scores(q, k, scale)               # [g, bs]
        kpos = (j * kv_pages + p) * bs + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        flash_update(m_ref, l_ref, acc_ref, s, kpos <= ctx, v)

    @pl.when(j == n_steps - 1)
    def _finish():
        o_ref[0, 0] = flash_finish(m_ref, l_ref, acc_ref, o_ref.dtype)


def paged_decode_attention(q, pool_kv, block_tables, ctx, *,
                           kv_pages: Optional[int] = None,
                           n_buffers: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """q [B, nq, hd] (ONE new token per sequence); pool_kv [n_blocks, nk,
    2, block_size, hd] fused (new KV already written at logical position
    ctx); block_tables [B, M] int32 physical block ids
    (scratch-padded); ctx [B] int32.  Returns [B, nq, hd].

    kv_pages — physical blocks fetched + folded per grid step;
    n_buffers — VMEM ring slots (1 = serial fetch->compute, 2/4 = the
    next step's DMA overlaps this step's flash update).  Both default
    from the env knobs in :mod:`repro.kernels.ops`."""
    kv_pages = paged_kv_pages() if kv_pages is None else kv_pages
    n_buffers = paged_n_buffers() if n_buffers is None else n_buffers
    interpret = resolve_interpret() if interpret is None else interpret
    B, nq, hd = q.shape
    nk, bs = pool_kv.shape[1], pool_kv.shape[3]
    M = block_tables.shape[1]
    kv_pages = max(1, min(kv_pages, M))
    g = nq // nk
    qh = q.reshape(B, nk, g, hd)
    n_steps = -(-M // kv_pages)
    grid = (B, nk, n_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # ctx, block_tables
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda b, h, j, c_ref, bt_ref: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # pool: kernel-side DMA
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, j, c_ref, bt_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((n_buffers, kv_pages, 2, bs, hd), pool_kv.dtype),
            pltpu.SemaphoreType.DMA((n_buffers, kv_pages)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_entries=M, kv_pages=kv_pages,
                          n_buffers=n_buffers, n_steps=n_steps,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nk, g, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.asarray(ctx, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      qh, pool_kv)
    return out.reshape(B, nq, hd)
