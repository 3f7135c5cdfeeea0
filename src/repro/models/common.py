"""Shared model primitives: norms, RoPE, attention math, cache plumbing, FFNs.

Everything here is pure-jnp (these double as the oracles the Pallas kernels
are validated against).  Attention helpers come in two flavours:

* *batched* — ``[B, L, ...]`` tensors where cache row ``b`` belongs to batch
  row ``b`` (training / batched prefill / batched decode);
* *packed* — SARATHI hybrid batches, a flat ``[T, ...]`` token axis split into
  one prefill chunk and ``D`` piggybacked decode tokens (see
  ``repro.core.batch``).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (shape[-2] == fan_in for 2-D weights)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, shape, dtype):
    # 1/sqrt(d_model) keeps tied-unembedding logits O(1)
    scale = 1.0 / math.sqrt(shape[-1])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_sin_cos(positions, head_dim: int, theta: float):
    """positions [...,] -> (sin, cos) of shape [..., head_dim//2], fp32."""
    half = head_dim // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # [..., half]
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, sin, cos):
    """x [..., n_heads, head_dim]; sin/cos broadcastable to [..., 1, hd//2]."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


# --------------------------------------------------------------------------
# attention math (pure-jnp oracle; the Pallas kernels mirror these)
# --------------------------------------------------------------------------
def gqa_attention(q, k, v, mask):
    """Grouped-query attention.

    q    [B, L, nq, hd]
    k, v [B, S, nk, hd]   (nq % nk == 0)
    mask [B, L, S] bool (True = attend) or broadcastable.

    Returns [B, L, nq, hd].
    """
    B, L, nq, hd = q.shape
    nk = k.shape[2]
    g = nq // nk
    qg = q.reshape(B, L, nk, g, hd)
    scores = jnp.einsum("blkgh,bskh->bklgs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    m = mask[:, None, :, None, :]                      # [B,1,L,1,S] -> k,g dims
    m = jnp.broadcast_to(m, scores.shape)
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no valid key (can happen for padded slots) -> zero output
    any_valid = jnp.any(m, axis=-1, keepdims=True)
    probs = jnp.where(any_valid, probs, 0.0)
    out = jnp.einsum("bklgs,bskh->blkgh", probs.astype(v.dtype), v)
    return out.reshape(B, L, nq, hd)


def blocked_gqa_attention(q, k, v, q_pos, *, causal: bool = True,
                          window: Optional[int] = None,
                          qb: int = 128, kb: int = 4096):
    """Memory-efficient (flash-style) GQA in pure XLA: double scan over
    query and key blocks with an online softmax — O(qb*kb) live scores
    instead of O(Lq*S).  This is the portable path of the batched,
    training and dense slot paths (the multi-pod dry-run compiles it); a
    paged pool is read by ``blocks.paged_attention`` instead, the same
    online softmax over tiles of its block tables.

    q     [B, Lq, nq, hd]
    k, v  [B, S, nk, hd]
    q_pos [B, Lq] absolute positions; key position j is ``arange(S)``;
    mask: j <= q_pos (if causal) and j > q_pos - window (if window).
    """
    B, Lq, nq, hd = q.shape
    S, nk = k.shape[1], k.shape[2]
    g = nq // nk
    qb = min(qb, Lq)
    kb = min(kb, S)
    pq = (-Lq) % qb
    pk = (-S) % kb
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    qpf = jnp.pad(q_pos, ((0, 0), (0, pq)), constant_values=-1)
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nQ, nK = (Lq + pq) // qb, (S + pk) // kb
    scale = 1.0 / math.sqrt(hd)

    q_blocks = jnp.moveaxis(qf.reshape(B, nQ, qb, nk, g, hd), 1, 0)
    qp_blocks = jnp.moveaxis(qpf.reshape(B, nQ, qb), 1, 0)
    k_blocks = jnp.moveaxis(kf.reshape(B, nK, kb, nk, hd), 1, 0)
    v_blocks = jnp.moveaxis(vf.reshape(B, nK, kb, nk, hd), 1, 0)
    kpos = jnp.arange(nK * kb, dtype=jnp.int32).reshape(nK, kb)

    def outer(_, qx):
        qblk, qpblk = qx                               # [B,qb,nk,g,hd], [B,qb]
        m0 = jnp.full((B, qb, nk, g), -1e30, jnp.float32)
        l0 = jnp.zeros((B, qb, nk, g), jnp.float32)
        a0 = jnp.zeros((B, qb, nk, g, hd), jnp.float32)

        # flash-style backward: recompute scores/probs per block instead of
        # saving them (only the small online-softmax carries persist)
        @jax.checkpoint
        def inner(carry, kx):
            m, l, acc = carry
            kblk, vblk, kp = kx
            s = jnp.einsum("bqkgh,bskh->bqkgs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            valid = kp[None, None, :] < S                 # drop kv padding
            if causal:
                valid = valid & (kp[None, None, :] <= qpblk[:, :, None])
            if window is not None:
                valid = valid & (kp[None, None, :]
                                 > qpblk[:, :, None] - window)
            valid = valid[:, :, None, None, :]            # [B,qb,1,1,kb]
            s = jnp.where(valid, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqkgs,bskh->bqkgh", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (m, l, acc).__class__((m_new, l, acc)), None

        (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0),
                                      (k_blocks, v_blocks, kpos))
        out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None],
                                                            1e-30), 0.0)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(outer, None, (q_blocks, qp_blocks))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Lq + pq, nk, g, hd)
    return out[:, :Lq].reshape(B, Lq, nq, hd)


def causal_cache_mask(q_pos, kv_len: int, window: Optional[int] = None):
    """Mask for queries at absolute positions ``q_pos`` [B, L] attending a
    cache laid out 0..kv_len-1 by absolute position.  True = attend.
    """
    cols = jnp.arange(kv_len, dtype=jnp.int32)[None, None, :]
    qp = q_pos[:, :, None]
    m = cols <= qp
    if window is not None:
        m = m & (cols > qp - window)
    return m


def ring_cache_mask(q_pos, cache_pos, window: int):
    """Mask for a ring-buffer window cache.

    q_pos     [B, L]  absolute query positions
    cache_pos [B, W]  absolute position stored in each ring slot (-1 = empty)
    """
    qp = q_pos[:, :, None]
    cp = cache_pos[:, None, :]
    return (cp >= 0) & (cp <= qp) & (cp > qp - window)


# --------------------------------------------------------------------------
# KV-cache plumbing
# --------------------------------------------------------------------------
def write_kv_rows(cache, new, start):
    """cache [B, S, nk, hd], new [B, L, nk, hd], start [B] -> updated cache."""
    def row(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))
    return jax.vmap(row)(cache, new, start.astype(jnp.int32))


def write_kv_slot(cache, new, slot, start):
    """Write one sequence's C new tokens into cache row ``slot`` at ``start``.

    cache [R, S, nk, hd], new [C, nk, hd]; slot/start scalars (traced ok).
    Rows landing past the cache length are DROPPED: a padded chunk whose
    static width spills past max_len (e.g. an unaligned final chunk from a
    budget scheduler) must not clamp into live positions the way a naive
    dynamic_update_slice would (it clamps ``start`` and rewrites context).
    Implemented with contiguous slice ops (not a per-token scatter, which
    XLA can't vectorise): clamp the window to fit, rotate ``new`` so valid
    tokens stay at their absolute positions, and blend the wrapped lanes
    with the window's previous contents.
    """
    S = cache.shape[1]
    C = new.shape[0]
    start_c = jnp.clip(start, 0, max(S - C, 0))
    d = start - start_c                  # spill: 0 unless the pad overruns
    rolled = jnp.roll(new, d, axis=0)
    old = jax.lax.dynamic_slice(
        cache, (slot, start_c, 0, 0), (1, C) + cache.shape[2:])[0]
    keep_old = jnp.arange(C, dtype=jnp.int32)[:, None, None] < d
    return jax.lax.dynamic_update_slice(
        cache, jnp.where(keep_old, old, rolled)[None],
        (slot, start_c, 0, 0))


def gather_block_rows(pool, block_tables):
    """Fused paged pool -> dense per-token rows: pool [N, nk, 2, bs, hd]
    x tables [..., M] -> [..., M * bs, nk, 2, hd] in logical-position
    order (the kernel oracles' read); :func:`split_fused_kv` then yields
    the K and V rows."""
    blocks = pool[jnp.asarray(block_tables, jnp.int32)]
    rows = jnp.moveaxis(blocks, -2, -4)          # [..., M, bs, nk, 2, hd]
    return rows.reshape(rows.shape[:-5] + (rows.shape[-5] * rows.shape[-4],)
                        + rows.shape[-3:])


def fuse_kv(k, v):
    """Per-token rows of the fused paged pool: k, v [..., nk, hd] ->
    [..., nk, 2, hd] (K at pair index 0, V at 1) — what one token's write
    ``pool[block, :, :, offset]`` stores.  The pool keeps head and pair on
    major axes (``[N, nk, 2, bs, hd]``), so one head's page is a single
    DMA of whole ``[bs, hd]`` tiles, and the head axis splits over the
    model axis without cutting a (K, V) pair."""
    return jnp.stack([k, v], axis=-2)


def split_fused_kv(rows):
    """Inverse of :func:`fuse_kv`: [..., nk, 2, hd] -> (k, v) each
    [..., nk, hd].  Pure slicing — bit-exact round trip."""
    return rows[..., 0, :], rows[..., 1, :]


def write_kv_scatter(cache, new, slots, positions):
    """Scatter one token per row: cache[slots[d], positions[d]] = new[d].

    cache [R, S, nk, hd], new [D, nk, hd], slots/positions [D].
    """
    return cache.at[slots, positions].set(new)


def write_ring(cache, cache_pos, new, new_pos, start_slot_axis=None):
    """Ring-buffer write for window caches (batched rows).

    cache     [B, W, nk, hd]; cache_pos [B, W]
    new       [B, L, nk, hd]; new_pos   [B, L] absolute positions
    """
    W = cache.shape[1]
    idx = (new_pos % W).astype(jnp.int32)                    # [B, L]
    b = jnp.arange(cache.shape[0], dtype=jnp.int32)[:, None]
    b = jnp.broadcast_to(b, idx.shape)
    cache = cache.at[b, idx].set(new)
    cache_pos = cache_pos.at[b, idx].set(new_pos.astype(jnp.int32))
    return cache, cache_pos


# --------------------------------------------------------------------------
# feed-forward networks
# --------------------------------------------------------------------------
def init_glu_ffn(key, d_model: int, d_ff: int, dtype, bias: bool = False):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }
    if bias:
        p["b_gate"] = jnp.zeros((d_ff,), dtype)
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def glu_ffn(p, x, act: str = "silu"):
    """``(act(x Wg + bg) * (x Wu + bu)) Wd + bd``; the biases only where
    the parameters hold them (``init_glu_ffn(bias=True)``)."""
    a = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    g = x @ p["w_gate"]
    if "b_gate" in p:
        g = g + p["b_gate"]
    g = a(g)
    u = x @ p["w_up"]
    if "b_up" in p:
        u = u + p["b_up"]
    y = (g * u) @ p["w_down"]
    return y + p["b_down"] if "b_down" in p else y


def init_mlp_ffn(key, d_model: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w1": dense_init(k1, (d_model, d_ff), dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": dense_init(k2, (d_ff, d_model), dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


def mlp_ffn(p, x, act: str = "relu"):
    a = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[act]
    return a(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------
def take_along_rows(cache, slots):
    """Gather cache rows for decode slots: cache [R, ...] -> [D, ...]."""
    return cache[slots]


def segsum(x):
    """Stable 'segment sum' used by SSD: out[..., i, j] = sum_{k=j+1..i} x[..., k]
    for j < i, 0 on diagonal, -inf above.  x [..., L] -> [..., L, L].
    """
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    i = jnp.arange(L)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, out, -jnp.inf)
