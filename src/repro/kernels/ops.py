"""Shared kernel-side helpers + jit'd public wrappers for the Pallas kernels.

The flash online-softmax inner loop (init / rescale-accumulate / finish
epilogue) is identical across the decode, chunked-prefill and paged
kernels, so it lives here once and every kernel body composes it with its
own masking and block-fetch logic.

On a TPU backend the kernels compile natively; on any other backend they
run in ``interpret=True`` mode (the kernel body executed op-by-op), which
is how the CPU test suite validates them against the ``ref.py`` oracles.
``REPRO_PALLAS_INTERPRET=0|1`` overrides that platform default either way.
The backend query is not guarded: a JAX that cannot start its backend
raises here rather than quietly switching the kernels to interpret mode.

The fused-paged kernels' tile knobs are env-tunable:

* ``REPRO_PAGED_KV_PAGES`` — physical KV blocks fetched + folded per grid
  step (default 1: one page per step);
* ``REPRO_PAGED_KV_BUFFERS`` — VMEM ring slots for the KV page DMAs
  (1 = serial fetch->compute, default 2 = double-buffered, 4 = quad);
* ``REPRO_PAGED_Q_BLOCK`` — query-tile rows for the chunked-prefill
  kernel (default 128; clamped/validated against the chunk length).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import env

NEG = -1e30


def resolve_interpret() -> bool:
    """Interpret-vs-compile for the Pallas kernels: compiled natively on a
    TPU backend, interpreted elsewhere (CPU CI), with
    ``REPRO_PALLAS_INTERPRET=0|1`` forcing either mode."""
    v = env.get("REPRO_PALLAS_INTERPRET")
    if v in ("0", "false"):
        return False
    if v in ("1", "true"):
        return True
    return jax.default_backend() != "tpu"


def paged_kv_pages() -> int:
    return env.get("REPRO_PAGED_KV_PAGES")


def paged_n_buffers() -> int:
    return env.get("REPRO_PAGED_KV_BUFFERS")


def paged_q_block() -> int:
    return env.get("REPRO_PAGED_Q_BLOCK")


# --------------------------------------------------------------------------
# flash online-softmax building blocks (used INSIDE Pallas kernel bodies)
# --------------------------------------------------------------------------
def flash_init(m_ref, l_ref, acc_ref):
    """First-KV-block epilogue: reset the running max / sum / accumulator."""
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def flash_scores(q, k, scale: float):
    """Masked-later attention scores for one tile: q [r, hd] x k [bk, hd]
    -> fp32 [r, bk]."""
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def flash_update(m_ref, l_ref, acc_ref, s, mask, v):
    """One online-softmax step: fold the tile's scores ``s`` [r, bk]
    (validity ``mask``) and values ``v`` [bk, hd] into the running state."""
    s = jnp.where(mask, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def flash_finish(m_ref, l_ref, acc_ref, dtype):
    """Last-KV-block epilogue: normalised output [r, hd] (all-masked rows
    -> 0, matching the oracle's padded-slot behaviour)."""
    l = l_ref[...]
    out = jnp.where(l[:, None] > 0,
                    acc_ref[...] / jnp.maximum(l[:, None], 1e-30), 0.0)
    return out.astype(dtype)


# --------------------------------------------------------------------------
# jit'd public wrappers
# --------------------------------------------------------------------------
# deferred imports: the kernel modules import the flash helpers above, so
# they must come after those definitions (benign module-level cycle)
from repro.kernels import chunked_prefill_attention as _cpa  # noqa: E402
from repro.kernels import decode_attention as _da            # noqa: E402
from repro.kernels import paged_chunked_prefill_attention as _pcpa  # noqa: E402
from repro.kernels import paged_decode_attention as _pda     # noqa: E402


@functools.partial(jax.jit, static_argnames=("bq", "bk"))
def chunked_prefill_attention(q, k, v, start, *, bq: int = 128,
                              bk: int = 128):
    return _cpa.chunked_prefill_attention(
        q, k, v, start, bq=bq, bk=bk, interpret=resolve_interpret())


@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, ctx, *, bk: int = 128):
    return _da.decode_attention(q, k, v, ctx, bk=bk,
                                interpret=resolve_interpret())


@functools.partial(jax.jit,
                   static_argnames=("bq", "kv_pages", "n_buffers"))
def paged_chunked_prefill_attention(q, pool_kv, block_table, start, *,
                                    bq=None, kv_pages=None, n_buffers=None):
    return _pcpa.paged_chunked_prefill_attention(
        q, pool_kv, block_table, start, bq=bq, kv_pages=kv_pages,
        n_buffers=n_buffers, interpret=resolve_interpret())


@functools.partial(jax.jit, static_argnames=("kv_pages", "n_buffers"))
def paged_decode_attention(q, pool_kv, block_tables, ctx, *,
                           kv_pages=None, n_buffers=None):
    return _pda.paged_decode_attention(
        q, pool_kv, block_tables, ctx, kv_pages=kv_pages,
        n_buffers=n_buffers, interpret=resolve_interpret())
