"""Layer blocks for every architecture family.

A *layer* is ``x += mixer(norm(x)); x += ffn(norm(x))`` (pre-norm), where the
mixer is one of:

* ``attn``        — full-cache causal GQA self-attention
* ``attn_swa``    — sliding-window self-attention over a ring-buffer cache
* ``cross``       — cross-attention over per-request memory KV (VLM / enc-dec)
* ``rglru``       — Griffin/RecurrentGemma gated linear recurrence (+conv)
* ``ssd``         — Mamba-2 state-space duality block (mixer and ffn in one)
* ``enc``         — bidirectional encoder self-attention (no cache)

and the ffn is ``glu`` (SwiGLU/GeGLU), ``mlp`` (relu/gelu), ``moe``
(capacity-factor top-k dispatch) or ``none``.

Every mixer implements BOTH interfaces:

* batched:  x [B, L, d], cache rows == batch rows, per-row ``start``;
* packed:   x [T, d] — a SARATHI hybrid batch (one chunk + D decodes).

The packed path is where decode-maximal batching happens: projections and
FFNs act on the packed [T, d] matrix (fused linear ops), mixing cores split
the chunk and decode segments.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.packed import PackedBatch


# ==========================================================================
# attention mixers
# ==========================================================================
def init_attention(cfg: ModelConfig, key, dtype) -> Dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": cm.dense_init(kq, (d, qd), dtype),
        "wk": cm.dense_init(kk, (d, kvd), dtype),
        "wv": cm.dense_init(kv, (d, kvd), dtype),
        "wo": cm.dense_init(ko, (qd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def _qkv(cfg, p, x):
    """Project tokens to q/k/v heads.  x [..., d]."""
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    hd = cfg.head_dim
    q = q.reshape(*x.shape[:-1], cfg.n_heads, hd)
    k = k.reshape(*x.shape[:-1], cfg.n_kv_heads, hd)
    v = v.reshape(*x.shape[:-1], cfg.n_kv_heads, hd)
    return q, k, v


def out_proj(p, out):
    """Attention heads ``out`` [..., q_dim] back to the model width:
    ``out Wo`` (+ ``bo`` where the parameters hold it).  Under tensor
    parallelism ``Wo`` is row-parallel and ``bo`` replicated, so the bias
    is added once, to the reduced sum."""
    y = out @ p["wo"]
    return y + p["bo"] if "bo" in p else y


def init_attn_cache(cfg: ModelConfig, rows: int, max_len: int, dtype) -> Dict:
    shp = (rows, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


# the paged pool leaf's key: it marks the fused block layout, so the layer
# scan, the packed path and the engine's block copies dispatch on it
POOL_KEY = "pkv"
_LANES = 128          # lanes of a TPU vector register: a tile's minor axis


def paged_block_shape(n_kv_heads: int, block_size: int,
                      head_dim: int) -> Tuple[int, ...]:
    """Stored shape of one block of the fused paged pool.

    ``[nk, 2, bs, hd]``, unless ``hd`` is no multiple of 128 lanes while a
    ``[bs, hd]`` page is: then ``[nk, 2, bs * hd // 128, 128]``, the same
    bytes in row-major order with 128 // hd tokens to a lane row.  A page
    of half-empty lanes (``hd`` = 64) makes the TPU lay the pool out with
    its block axis minor, so every block gather or scatter in the layer
    scan needs the whole stacked pool copied into a padded layout and
    back, twice the pool's bytes each step.  Folded, the pool keeps the
    default layout with no padding.  :func:`unfold_blocks` undoes the
    fold on gathered blocks."""
    if head_dim % _LANES and (block_size * head_dim) % _LANES == 0:
        return (n_kv_heads, 2, block_size * head_dim // _LANES, _LANES)
    return (n_kv_heads, 2, block_size, head_dim)


def unfold_blocks(blocks, head_dim: int):
    """Stored pool blocks ``[..., nk, 2, r, w]`` -> ``[..., nk, 2, bs,
    hd]`` (a reshape: :func:`paged_block_shape` keeps row-major order)."""
    r, w = blocks.shape[-2:]
    return blocks.reshape(blocks.shape[:-2] + (r * w // head_dim, head_dim))


def init_paged_attn_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                          dtype) -> Dict:
    """Pooled KV for full-attention layers: ONE fused leaf ``[n_blocks,
    nk, 2, block_size, hd]`` (K at pair index 0, V at 1; stored as
    :func:`paged_block_shape` says), addressed
    through per-request block tables (``repro.cache``).  One leaf instead
    of split ``pk``/``pv`` halves the block-table DMA count in the Pallas
    kernels and halves the gather/scatter count on copy-on-write forks.
    Head and pair sit on major axes so one head's page is whole
    ``[block_size, hd]`` tiles (a TPU DMA may not cut the tiled minor
    two axes) and TP splits the head axis without cutting a pair.  The
    key ``pkv`` (vs dense ``k``/``v``) marks the layout, so the packed
    path and the engine's slot reset dispatch structurally."""
    shp = (n_blocks,) + paged_block_shape(cfg.n_kv_heads, block_size,
                                          cfg.head_dim)
    return {POOL_KEY: jnp.zeros(shp, dtype)}


def init_swa_cache(cfg: ModelConfig, rows: int, window: int, dtype) -> Dict:
    shp = (rows, window, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype),
            "pos": jnp.full((rows, window), -1, jnp.int32)}


def init_cross_cache(cfg: ModelConfig, rows: int, dtype) -> Dict:
    shp = (rows, cfg.n_frontend_tokens, cfg.n_kv_heads, cfg.head_dim)
    return {"ck": jnp.zeros(shp, dtype), "cv": jnp.zeros(shp, dtype)}


# ----------------------------------------------------------- batched: attn
def attn_batched(cfg, p, x, cache, start, *, train: bool,
                 window: Optional[int] = None, causal: bool = True):
    """x [B, L, d]; cache rows == B; start [B] absolute offset per row."""
    B, L, _ = x.shape
    q, k, v = _qkv(cfg, p, x)
    pos = start[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    sin, cos = cm.rope_sin_cos(pos, cfg.head_dim, cfg.rope_theta)
    q = cm.apply_rope(q, sin, cos)
    k = cm.apply_rope(k, sin, cos)

    if train or cache is None:
        out = cm.blocked_gqa_attention(q, k, v, pos, causal=causal,
                                       window=window)
        new_cache = cache
    elif window is not None:
        # ring-buffer window cache: attend [in-flight L ‖ ring W], then write
        ring_k, ring_v, ring_pos = cache["k"], cache["v"], cache["pos"]
        i = pos[:, :, None]
        j = pos[:, None, :]
        mask_in = (j <= i) & (j > i - window)
        mask_ring = cm.ring_cache_mask(pos, ring_pos, window)
        kk = jnp.concatenate([k, ring_k], axis=1)
        vv = jnp.concatenate([v, ring_v], axis=1)
        mask = jnp.concatenate([mask_in, mask_ring], axis=2)
        out = cm.gqa_attention(q, kk, vv, mask)
        if L >= window:
            k_w, v_w, p_w = (k[:, -window:], v[:, -window:], pos[:, -window:])
        else:
            k_w, v_w, p_w = k, v, pos
        ring_k, ring_pos = cm.write_ring(ring_k, ring_pos, k_w, p_w)
        ring_v, _ = cm.write_ring(ring_v, cache["pos"], v_w, p_w)
        new_cache = {"k": ring_k, "v": ring_v, "pos": ring_pos}
    else:
        ck = cm.write_kv_rows(cache["k"], k, start)
        cv = cm.write_kv_rows(cache["v"], v, start)
        out = cm.blocked_gqa_attention(q, ck, cv, pos)
        new_cache = {"k": ck, "v": cv}

    return out_proj(p, out.reshape(B, L, cfg.q_dim)), new_cache


def cross_batched(cfg, p, x, cache, *, memory=None):
    """Cross-attention.  memory [B, F, d] if provided (train / first prefill);
    otherwise read the per-row cached cross KV."""
    B, L, _ = x.shape
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, L, cfg.n_heads, cfg.head_dim)
    if memory is not None:
        k = (memory @ p["wk"]).reshape(B, -1, cfg.n_kv_heads, cfg.head_dim)
        v = (memory @ p["wv"]).reshape(B, -1, cfg.n_kv_heads, cfg.head_dim)
        new_cache = cache if cache is None else {"ck": k, "cv": v}
    else:
        k, v = cache["ck"], cache["cv"]
        new_cache = cache
    F = k.shape[1]
    mask = jnp.ones((B, L, F), bool)
    out = cm.gqa_attention(q, k, v, mask)
    return out_proj(p, out.reshape(B, L, cfg.q_dim)), new_cache


# ------------------------------------------------------------ packed: attn
from repro import env

_PAGED_ATTN_BACKENDS = env.REGISTRY["REPRO_PAGED_ATTN_BACKEND"].choices

# Mesh hint for the paged Pallas kernels under tensor parallelism.  GSPMD
# cannot partition a pallas_call, so when a TP engine runs the pallas
# backend the kernel invocations are wrapped in shard_map over the mesh's
# "model" axis (kv heads stay whole per shard — the engine enforces
# nk % tp == 0 up front).  Set by the engines immediately before
# each jitted step call (trace-time read, like the MoE dispatch hint).
_PAGED_ATTN_MESH = None


def set_paged_attn_mesh(mesh) -> None:
    global _PAGED_ATTN_MESH
    _PAGED_ATTN_MESH = mesh


def _paged_attn_backend() -> str:
    """Attention backend for the paged packed path: "xla" (portable gather
    + blocked flash attention, the default) or "pallas" (the block-table
    scalar-prefetch kernels of repro.kernels — native on TPU, interpret
    mode elsewhere).  Unrecognized values raise (in the registry's typed
    read) instead of silently falling through to xla."""
    return env.get("REPRO_PAGED_ATTN_BACKEND")


def _paged_shard_mesh(pool_kv):
    """The mesh to shard_map the pallas kernels over, or None for the
    single-device call.  Requires whole kv heads per shard — the
    placement layer rejects nk % tp != 0 before any engine is built, so
    this only double-checks divisibility at trace time."""
    mesh = _PAGED_ATTN_MESH
    if mesh is None:
        return None
    tp = mesh.shape.get("model", 1)
    if tp <= 1:
        return None
    nk = pool_kv.shape[1]
    if nk % tp:
        raise ValueError(
            f"paged pallas backend under tp={tp} needs n_kv_heads "
            f"({nk}) divisible by tp so kv heads stay whole per shard")
    return mesh


def _shard_map_heads(fn, mesh, n_table_args):
    """shard_map ``fn(q, pool_kv, <tables...>, scalar)`` over the kv-head
    axis: q [.., nq, hd] splits heads, pool [N, nk, 2, bs, hd] splits its
    head axis, tables/ctx replicate.  Each shard runs the unmodified
    single-device kernel on its local heads (block tables are physical —
    identical on every shard), so tp>1 output == concat of per-shard
    outputs over the head axis."""
    P = jax.sharding.PartitionSpec
    reps = (P(),) * n_table_args
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "model", None),
                  P(None, "model", None, None, None)) + reps,
        out_specs=P(None, "model", None), check_vma=False)


@dataclasses.dataclass(frozen=True)
class LayerPool:
    """One layer's fused paged pool as the packed path addresses it.

    ``pool`` is a pool leaf as stored (:func:`paged_block_shape`) with its
    leading axes merged, ``[N', nk, 2, r, w]``, and the layer's blocks are
    ``pool[base:base + n_blocks]``: inside the layer scan ``pool`` is the
    whole stacked pool the scan carries and ``base`` the layer's first
    block; a tail layer's own pool has ``base`` 0.  Reads gather and
    writes scatter whole blocks at ``base + block``, so no op takes a
    layer's pool out of the stack or writes one back.  Not a pytree: it
    lives inside one traced layer (:func:`carry_in`, :func:`carry_out`)."""
    pool: jax.Array
    base: jax.Array | int
    n_blocks: int

    def block_size(self, hd: int) -> int:
        return self.pool.shape[-2] * self.pool.shape[-1] // hd

    def blocks(self, idx, hd: int):
        """Blocks ``idx`` of this layer, ``[*idx.shape, nk, 2, bs, hd]``."""
        return unfold_blocks(self.pool[self.base + idx], hd)

    def rows(self, tables, hd: int):
        """Per-token rows through block ``tables`` [..., M] ->
        ``[..., M * bs, nk, 2, hd]``, as :func:`common.gather_block_rows`
        reads a layer's own pool."""
        return cm.block_rows(self.blocks(jnp.asarray(tables, jnp.int32), hd))

    def whole(self, hd: int):
        """This layer's pool ``[N, nk, 2, bs, hd]`` (the Pallas kernels'
        operand): a read-only slice, or the pool itself for a tail layer."""
        pool = jax.lax.dynamic_slice_in_dim(self.pool, self.base,
                                            self.n_blocks)
        return unfold_blocks(pool, hd)

    def write_rows(self, phys, blk, off, kv) -> "LayerPool":
        """Block read-modify-write: gather blocks ``phys`` [B], store the
        token rows ``kv`` [T, nk, 2, hd] at (``blk`` [T] into ``phys``,
        offset ``off`` [T]), scatter the whole blocks back.  Only the
        scratch block 0 may repeat in ``phys``."""
        at = self.base + phys
        blocks = self.blocks(phys, kv.shape[-1]).at[blk, :, :, off].set(kv)
        blocks = blocks.reshape(blocks.shape[:1] + self.pool.shape[1:])
        return dataclasses.replace(self, pool=self.pool.at[at].set(blocks))


def _is_pool(path) -> bool:
    return bool(path) and getattr(path[-1], "key", None) == POOL_KEY


def carry_in(path, leaf, layer=None):
    """What a layer gets of the cache leaf at ``path``: layer ``layer`` of
    a leaf stacked over the scanned layers, or a tail layer's own leaf
    (``layer`` None).  The paged pool leaf comes as a :class:`LayerPool`
    over the whole leaf: slicing a layer's pool out and writing it back
    makes the TPU compiler copy and re-lay out that pool on every layer.
    Every other leaf (dense ``k``/``v``, window rings, recurrent and SSD
    state) is sliced."""
    if _is_pool(path):
        if layer is None:
            return LayerPool(leaf, 0, leaf.shape[0])
        L, N = leaf.shape[:2]
        return LayerPool(leaf.reshape((L * N,) + leaf.shape[2:]),
                         layer * N, N)
    if layer is None:
        return leaf
    return jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)


def carry_out(leaf, new, layer=None):
    """Inverse of :func:`carry_in`: the leaf with the layer's update
    ``new`` in it (the returned pool as it stands, a slice written back
    at ``layer``, or a tail layer's new leaf)."""
    if isinstance(new, LayerPool):
        return new.pool.reshape(leaf.shape)
    if layer is None:
        return new
    return jax.lax.dynamic_update_index_in_dim(leaf, new, layer, 0)


def _write_chunk(ref: LayerPool, pk: PackedBatch, kv) -> LayerPool:
    """Write the chunk's token rows ``kv`` [C, nk, 2, hd] block by block.

    C consecutive positions touch at most ``(C + 2bs - 2) // bs`` logical
    blocks from ``chunk_start // bs``.  A block with no valid token (past
    ``chunk_len`` or past ``max_len``) is routed to the scratch block 0,
    so only the request's own live blocks are rewritten; rows past
    ``chunk_len`` inside a live block are padding that a later write
    overwrites before any query sees it."""
    C = kv.shape[0]
    bs = ref.block_size(kv.shape[-1])
    M = pk.chunk_blocks.shape[0]
    first = pk.chunk_start // bs
    lb = first + jnp.arange((C + 2 * bs - 2) // bs, dtype=jnp.int32)
    live = (lb < M) & (lb * bs < pk.chunk_start + pk.chunk_len)
    phys = jnp.where(live, pk.chunk_blocks[jnp.clip(lb, 0, M - 1)], 0)
    cpos = pk.chunk_start + jnp.arange(C, dtype=jnp.int32)
    return ref.write_rows(phys, cpos // bs - first, cpos % bs, kv)


def _write_decodes(ref: LayerPool, pk: PackedBatch, kv) -> LayerPool:
    """Write each decode lane's row ``kv`` [D, nk, 2, hd] into its block
    at ``decode_ctx % bs``; padding lanes' tables name only block 0."""
    bs = ref.block_size(kv.shape[-1])
    bidx = (pk.decode_ctx // bs)[:, None]
    phys = jnp.take_along_axis(pk.decode_blocks, bidx, axis=1)[:, 0]
    lanes = jnp.arange(kv.shape[0], dtype=jnp.int32)
    return ref.write_rows(phys, lanes, pk.decode_ctx % bs, kv)


def _attn_packed_paged(cfg, p, q, k, v, pos, cache, pk: PackedBatch):
    """Block-table variant of the full-attention packed path.  The fused
    [nk, 2, hd] token rows are written first, the chunk's and then the
    decodes', by whole-block read-modify-writes; then the chunk and the
    decodes read, either via a fused-row gather + K/V split (XLA backend)
    or the fused-pool paged Pallas kernels.  Neither read can see the
    other segment's writes except in the scratch block: a decode writes
    only its own (copy-on-write forked) last block.  ``cache["pkv"]`` is
    a :class:`LayerPool` and so is the result's."""
    C, D = pk.num_chunk, pk.num_decode
    hd = cfg.head_dim
    ref = cache[POOL_KEY]
    with jax.named_scope("kv_write"):
        if C:
            ref = _write_chunk(ref, pk, cm.fuse_kv(k[:C], v[:C]))
        if D:
            ref = _write_decodes(ref, pk, cm.fuse_kv(k[C:], v[C:]))
    outs = []
    if _paged_attn_backend() == "pallas":
        from repro.kernels import ops as kops
        with jax.named_scope("kv_read"):
            pool = ref.whole(hd)
        mesh = _paged_shard_mesh(pool)
        if C:
            bq = 128 if C % 128 == 0 else C
            call = functools.partial(kops.paged_chunked_prefill_attention,
                                     bq=bq)
            if mesh is not None:
                call = _shard_map_heads(call, mesh, n_table_args=2)
            with jax.named_scope("attn"):
                outs.append(call(q[:C], pool, pk.chunk_blocks,
                                 pk.chunk_start))
        if D:
            call = kops.paged_decode_attention
            if mesh is not None:
                call = _shard_map_heads(call, mesh, n_table_args=2)
            with jax.named_scope("attn"):
                outs.append(call(q[C:], pool, pk.decode_blocks,
                                 pk.decode_ctx))
    else:
        if C:
            with jax.named_scope("kv_read"):
                row_k, row_v = cm.split_fused_kv(ref.rows(pk.chunk_blocks, hd))
            with jax.named_scope("attn"):
                outs.append(cm.blocked_gqa_attention(
                    q[None, :C], row_k[None], row_v[None], pos[None, :C])[0])
        if D:
            with jax.named_scope("kv_read"):
                gk, gv = cm.split_fused_kv(ref.rows(pk.decode_blocks, hd))
            with jax.named_scope("attn"):
                outs.append(cm.blocked_gqa_attention(
                    q[C:, None], gk, gv, pk.decode_ctx[:, None])[:, 0])
    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return out, {POOL_KEY: ref}


def attn_packed(cfg, p, x, cache, pk: PackedBatch,
                window: Optional[int] = None):
    """x [T, d] packed hybrid batch."""
    C, D = pk.num_chunk, pk.num_decode
    with jax.named_scope("qkv"):
        q, k, v = _qkv(cfg, p, x)
        pos = pk.positions()
        sin, cos = cm.rope_sin_cos(pos, cfg.head_dim, cfg.rope_theta)
        q = cm.apply_rope(q, sin, cos)
        k = cm.apply_rope(k, sin, cos)

    if POOL_KEY in cache:
        assert window is None, "window caches are slot-indexed, not paged"
        out, new_cache = _attn_packed_paged(cfg, p, q, k, v, pos, cache, pk)
        with jax.named_scope("o_proj"):
            return out_proj(p, out.reshape(C + D, cfg.q_dim)), new_cache

    outs = []
    if window is None:
        ck, cv = cache["k"], cache["v"]
        S = ck.shape[1]
        if C:
            ck = cm.write_kv_slot(ck, k[:C], pk.chunk_slot, pk.chunk_start)
            cv = cm.write_kv_slot(cv, v[:C], pk.chunk_slot, pk.chunk_start)
            row_k = jax.lax.dynamic_index_in_dim(ck, pk.chunk_slot, 0,
                                                 keepdims=True)
            row_v = jax.lax.dynamic_index_in_dim(cv, pk.chunk_slot, 0,
                                                 keepdims=True)
            out_c = cm.blocked_gqa_attention(q[None, :C], row_k, row_v,
                                             pos[None, :C])[0]
            outs.append(out_c)
        if D:
            ck = cm.write_kv_scatter(ck, k[C:], pk.decode_slots, pk.decode_ctx)
            cv = cm.write_kv_scatter(cv, v[C:], pk.decode_slots, pk.decode_ctx)
            gk = ck[pk.decode_slots]                      # [D, S, nk, hd]
            gv = cv[pk.decode_slots]
            out_d = cm.blocked_gqa_attention(
                q[C:, None], gk, gv, pk.decode_ctx[:, None])[:, 0]
            outs.append(out_d)
        new_cache = {"k": ck, "v": cv}
    else:
        rk, rv, rpos = cache["k"], cache["v"], cache["pos"]
        W = rk.shape[1]
        if C:
            cpos = pos[None, :C]
            row_k = jax.lax.dynamic_index_in_dim(rk, pk.chunk_slot, 0, True)
            row_v = jax.lax.dynamic_index_in_dim(rv, pk.chunk_slot, 0, True)
            row_p = jax.lax.dynamic_index_in_dim(rpos, pk.chunk_slot, 0, True)
            i = cpos[:, :, None]
            j = cpos[:, None, :]
            mask_in = (j <= i) & (j > i - window)
            mask_ring = cm.ring_cache_mask(cpos, row_p, window)
            kk = jnp.concatenate([k[None, :C], row_k], axis=1)
            vv = jnp.concatenate([v[None, :C], row_v], axis=1)
            mask = jnp.concatenate([mask_in, mask_ring], axis=2)
            out_c = cm.gqa_attention(q[None, :C], kk, vv, mask)[0]
            outs.append(out_c)
            n_w = min(C, W)
            # last n_w *valid* tokens (chunk may be padded past chunk_len);
            # padding writes are routed out-of-range and dropped
            start_w = jnp.maximum(pk.chunk_len - n_w, 0)
            k_w = jax.lax.dynamic_slice_in_dim(k, start_w, n_w, 0)
            v_w = jax.lax.dynamic_slice_in_dim(v, start_w, n_w, 0)
            p_w = jax.lax.dynamic_slice_in_dim(pos, start_w, n_w, 0)
            tok_idx = start_w + jnp.arange(n_w)
            valid_w = tok_idx < pk.chunk_len
            idx = jnp.where(valid_w, (p_w % W).astype(jnp.int32), W)
            slot_b = jnp.broadcast_to(pk.chunk_slot, idx.shape)
            rk = rk.at[slot_b, idx].set(k_w, mode="drop")
            rv = rv.at[slot_b, idx].set(v_w, mode="drop")
            rpos = rpos.at[slot_b, idx].set(p_w.astype(jnp.int32),
                                            mode="drop")
        if D:
            dpos = pk.decode_ctx
            idx = (dpos % W).astype(jnp.int32)
            rk = rk.at[pk.decode_slots, idx].set(k[C:])
            rv = rv.at[pk.decode_slots, idx].set(v[C:])
            rpos = rpos.at[pk.decode_slots, idx].set(dpos.astype(jnp.int32))
            gk = rk[pk.decode_slots]
            gv = rv[pk.decode_slots]
            gp = rpos[pk.decode_slots]
            mask = cm.ring_cache_mask(dpos[:, None], gp, window)
            out_d = cm.gqa_attention(q[C:, None], gk, gv, mask)[:, 0]
            outs.append(out_d)
        new_cache = {"k": rk, "v": rv, "pos": rpos}

    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    with jax.named_scope("o_proj"):
        return out_proj(p, out.reshape(C + D, cfg.q_dim)), new_cache


def cross_packed(cfg, p, x, cache, pk: PackedBatch):
    C, D = pk.num_chunk, pk.num_decode
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(C + D, cfg.n_heads, cfg.head_dim)
    outs = []
    if C:
        row_k = jax.lax.dynamic_index_in_dim(cache["ck"], pk.chunk_slot, 0, True)
        row_v = jax.lax.dynamic_index_in_dim(cache["cv"], pk.chunk_slot, 0, True)
        F = row_k.shape[1]
        mask = jnp.ones((1, C, F), bool)
        outs.append(cm.gqa_attention(q[None, :C], row_k, row_v, mask)[0])
    if D:
        gk = cache["ck"][pk.decode_slots]
        gv = cache["cv"][pk.decode_slots]
        F = gk.shape[1]
        mask = jnp.ones((D, 1, F), bool)
        outs.append(cm.gqa_attention(q[C:, None], gk, gv, mask)[:, 0])
    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return out_proj(p, out.reshape(C + D, cfg.q_dim)), cache


def compute_cross_kv(cfg, p, memory):
    """memory [F, d] (one request) -> (k, v) [F, nk, hd] for cache seeding."""
    k = (memory @ p["wk"]).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
    v = (memory @ p["wv"]).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
    return k, v


# ==========================================================================
# RG-LRU mixer (Griffin / RecurrentGemma recurrent block)
# ==========================================================================
_LRU_C = 8.0


def _lru_blocks(cfg: ModelConfig) -> Tuple[int, int]:
    """Block-diagonal gate structure (Griffin): one block per head."""
    nb = max(cfg.n_heads, 1)
    assert cfg.lru_width % nb == 0, (cfg.lru_width, nb)
    return nb, cfg.lru_width // nb


def init_rglru(cfg: ModelConfig, key, dtype) -> Dict:
    w = cfg.lru_width
    d = cfg.d_model
    nb, bw = _lru_blocks(cfg)
    ks = jax.random.split(key, 7)
    return {
        "w_in_rec": cm.dense_init(ks[0], (d, w), dtype),    # recurrent branch
        "w_in_gate": cm.dense_init(ks[1], (d, w), dtype),   # gelu branch
        "conv_w": cm.dense_init(ks[2], (cfg.ssm_conv_width, w), dtype,
                                scale=1.0 / math.sqrt(cfg.ssm_conv_width)),
        "conv_b": jnp.zeros((w,), dtype),
        # block-diagonal (per-head) gates, Griffin-style
        "w_a": cm.dense_init(ks[3], (nb, bw, bw), dtype, scale=1.0 / math.sqrt(bw)),
        "b_a": jnp.zeros((nb, bw), jnp.float32),
        "w_i": cm.dense_init(ks[4], (nb, bw, bw), dtype, scale=1.0 / math.sqrt(bw)),
        "b_i": jnp.zeros((nb, bw), jnp.float32),
        # Lambda parametrised so a ~ U(0.9, 0.999) at r=1 (Griffin init)
        "lam": jnp.asarray(
            jax.random.uniform(ks[5], (w,), jnp.float32, 0.3, 0.8), jnp.float32),
        "w_out": cm.dense_init(ks[6], (w, d), dtype),
    }


def init_rglru_cache(cfg: ModelConfig, rows: int, dtype) -> Dict:
    w = cfg.lru_width
    return {
        "h": jnp.zeros((rows, w), jnp.float32),
        "conv": jnp.zeros((rows, cfg.ssm_conv_width - 1, w), dtype),
    }


def _causal_conv(seq, conv_state, w, b, valid_len=None):
    """Depthwise causal conv1d.  seq [B, L, ch]; conv_state [B, cw-1, ch].

    If ``valid_len`` (scalar) is given, tokens at index >= valid_len are
    padding and the returned conv state is the last cw-1 *valid* inputs.
    """
    cw = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((seq.shape[0], cw - 1, seq.shape[2]), seq.dtype)
    else:
        pad = conv_state.astype(seq.dtype)
    full = jnp.concatenate([pad, seq], axis=1)            # [B, L+cw-1, ch]
    out = sum(full[:, i:i + seq.shape[1]] * w[i] for i in range(cw)) + b
    if cw == 1:
        return out, conv_state
    if valid_len is None:
        new_state = full[:, -(cw - 1):]
    else:
        # valid inputs end at index (cw-1) + valid_len in ``full``
        new_state = jax.lax.dynamic_slice_in_dim(
            full, valid_len, cw - 1, axis=1)
    return out, new_state


def _lru_scan(a, bx, h0):
    """h_t = a_t * h_{t-1} + bx_t, over axis 1.  a, bx [B, L, w] fp32."""
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2
    a_acc, h = jax.lax.associative_scan(combine, (a, bx), axis=1)
    if h0 is not None:
        h = h + a_acc * h0[:, None, :]
    return h


def rglru_core(p, u, h0, conv_state, valid_len=None):
    """u [B, L, w] recurrent-branch input (post in-proj).  Returns
    (y [B, L, w], h_final [B, w], new_conv_state).  Tokens at index >=
    ``valid_len`` (if given) are padding: they pass the state through
    unchanged (a=1, input 0)."""
    L = u.shape[1]
    xc, new_conv = _causal_conv(u, conv_state, p["conv_w"], p["conv_b"],
                                valid_len=valid_len)
    x32 = xc.astype(jnp.float32)
    nb, bw = p["w_a"].shape[0], p["w_a"].shape[1]
    xb = x32.reshape(*x32.shape[:-1], nb, bw)
    wa = p["w_a"].astype(jnp.float32)
    wi = p["w_i"].astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("blnc,ncd->blnd", xb, wa) + p["b_a"])
    i = jax.nn.sigmoid(jnp.einsum("blnc,ncd->blnd", xb, wi) + p["b_i"])
    r = r.reshape(x32.shape)
    i = i.reshape(x32.shape)
    log_a = -_LRU_C * jax.nn.softplus(p["lam"]) * r       # [B, L, w], <= 0
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * x32)
    if valid_len is not None:
        valid = (jnp.arange(L) < valid_len)[None, :, None]
        a = jnp.where(valid, a, 1.0)
        gated = jnp.where(valid, gated, 0.0)
    h = _lru_scan(a, gated, h0)
    return h.astype(u.dtype), h[:, -1], new_conv


def rglru_batched(cfg, p, x, cache, *, train: bool):
    B, L, _ = x.shape
    u = x @ p["w_in_rec"]
    gate = jax.nn.gelu(x @ p["w_in_gate"])
    h0 = None if (train or cache is None) else cache["h"]
    cs = None if (train or cache is None) else cache["conv"]
    y, h_fin, new_conv = rglru_core(p, u, h0, cs)
    out = (y * gate) @ p["w_out"]
    new_cache = cache if (train or cache is None) else \
        {"h": h_fin, "conv": new_conv}
    return out, new_cache


def rglru_packed(cfg, p, x, cache, pk: PackedBatch):
    C, D = pk.num_chunk, pk.num_decode
    u = x @ p["w_in_rec"]                                  # fused over [T]
    gate = jax.nn.gelu(x @ p["w_in_gate"])
    h_all, conv_all = cache["h"], cache["conv"]
    ys = []
    if C:
        h0 = jax.lax.dynamic_index_in_dim(h_all, pk.chunk_slot, 0, True)
        cs = jax.lax.dynamic_index_in_dim(conv_all, pk.chunk_slot, 0, True)
        y, h_fin, new_cs = rglru_core(p, u[None, :C], h0, cs,
                                      valid_len=pk.chunk_len)
        h_all = jax.lax.dynamic_update_index_in_dim(
            h_all, h_fin[0], pk.chunk_slot, 0)
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, new_cs[0], pk.chunk_slot, 0)
        ys.append(y[0])
    if D:
        h0 = h_all[pk.decode_slots]                        # [D, w]
        cs = conv_all[pk.decode_slots]                     # [D, cw-1, w]
        y, h_fin, new_cs = rglru_core(p, u[C:, None], h0, cs)
        h_all = h_all.at[pk.decode_slots].set(h_fin)
        conv_all = conv_all.at[pk.decode_slots].set(new_cs)
        ys.append(y[:, 0])
    y = jnp.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
    out = (y * gate) @ p["w_out"]
    return out, {"h": h_all, "conv": conv_all}


# ==========================================================================
# SSD mixer (Mamba-2) — mixer and "ffn" in one block
# ==========================================================================
def init_ssd(cfg: ModelConfig, key, dtype) -> Dict:
    """Projections are split per component (z/x/B/C/dt) so each can carry a
    clean PartitionSpec: d_inner and heads shard over the model axis,
    B/C (state projections) replicate."""
    d, di = cfg.d_model, cfg.d_inner
    g, n, nh = cfg.ssm_ngroups, cfg.ssm_state, cfg.n_ssm_heads
    cw = cfg.ssm_conv_width
    cscale = 1.0 / math.sqrt(cw)
    ks = jax.random.split(key, 10)
    return {
        "w_z": cm.dense_init(ks[0], (d, di), dtype),
        "w_x": cm.dense_init(ks[1], (d, di), dtype),
        "w_B": cm.dense_init(ks[2], (d, g * n), dtype),
        "w_C": cm.dense_init(ks[3], (d, g * n), dtype),
        "w_dt": cm.dense_init(ks[4], (d, nh), dtype),
        "conv_x_w": cm.dense_init(ks[5], (cw, di), dtype, scale=cscale),
        "conv_x_b": jnp.zeros((di,), dtype),
        "conv_B_w": cm.dense_init(ks[6], (cw, g * n), dtype, scale=cscale),
        "conv_B_b": jnp.zeros((g * n,), dtype),
        "conv_C_w": cm.dense_init(ks[7], (cw, g * n), dtype, scale=cscale),
        "conv_C_b": jnp.zeros((g * n,), dtype),
        "a_log": jnp.log(jnp.asarray(
            jax.random.uniform(ks[8], (nh,), jnp.float32, 1.0, 16.0))),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "w_out": cm.dense_init(ks[9], (di, d), dtype),
    }


def init_ssd_cache(cfg: ModelConfig, rows: int, dtype) -> Dict:
    di = cfg.d_inner
    g, n, nh = cfg.ssm_ngroups, cfg.ssm_state, cfg.n_ssm_heads
    hd = cfg.ssm_headdim
    cw = cfg.ssm_conv_width
    return {
        "state": jnp.zeros((rows, nh, hd, n), jnp.float32),
        "conv_x": jnp.zeros((rows, cw - 1, di), dtype),
        "conv_B": jnp.zeros((rows, cw - 1, g * n), dtype),
        "conv_C": jnp.zeros((rows, cw - 1, g * n), dtype),
    }


def ssd_scan(x, dt, a_neg, Bm, Cm, init_state, chunk: int):
    """Chunked SSD (Mamba-2 alg. 1).

    x   [B, L, nh, P]   dt [B, L, nh]   a_neg [nh] (negative reals)
    Bm, Cm [B, L, G, N] ; init_state [B, nh, P, N] or None.
    Returns (y [B, L, nh, P], final_state [B, nh, P, N]).  fp32 internally.
    """
    Bsz, L, nh, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hg = nh // G
    cl = min(chunk, L)
    pad = (-L) % cl
    if pad:
        zf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, Bm, Cm = zf(x), zf(dt), zf(Bm), zf(Cm)
    Lp = L + pad
    nc = Lp // cl

    x = x.astype(jnp.float32).reshape(Bsz, nc, cl, G, hg, P)
    dt = dt.astype(jnp.float32).reshape(Bsz, nc, cl, G, hg)
    Bm = Bm.astype(jnp.float32).reshape(Bsz, nc, cl, G, N)
    Cm = Cm.astype(jnp.float32).reshape(Bsz, nc, cl, G, N)
    a = a_neg.reshape(G, hg)
    dtA = dt * a                                            # [B,nc,cl,G,hg]
    dtx = dt[..., None] * x                                 # [B,nc,cl,G,hg,P]

    if init_state is None:
        init_state = jnp.zeros((Bsz, nh, P, N), jnp.float32)
    h0 = init_state.reshape(Bsz, G, hg, P, N)

    def body(h, inp):
        dtA_c, dtx_c, B_c, C_c = inp                        # leading dim B
        cum = jnp.cumsum(dtA_c, axis=1)                     # [B,cl,G,hg] incl.
        total = cum[:, -1]                                  # [B,G,hg]
        # inter-chunk: y_t += C_t . h * exp(cum_t)
        y_inter = jnp.einsum("btgn,bghpn->btghp", C_c, h) \
            * jnp.exp(cum)[..., None]
        # intra-chunk: scores[t,s] = (C_t.B_s) * exp(cum_t - cum_s), s <= t
        seg = cm.segsum(jnp.moveaxis(dtA_c, 1, -1))         # [B,G,hg,cl,cl]
        decay = jnp.exp(seg)
        CB = jnp.einsum("btgn,bsgn->bgts", C_c, B_c)        # [B,G,cl,cl]
        scores = CB[:, :, None] * decay                     # [B,G,hg,cl,cl]
        y_intra = jnp.einsum("bghts,bsghp->btghp", scores, dtx_c)
        # state update: h' = exp(total) h + sum_s exp(total - cum_s) B_s dtx_s
        w = jnp.exp(total[:, None] - cum)                   # [B,cl,G,hg]
        h_new = jnp.exp(total)[..., None, None] * h + \
            jnp.einsum("bsgn,bsghp,bsgh->bghpn", B_c, dtx_c, w)
        return h_new, y_inter + y_intra

    xs = (jnp.moveaxis(dtA, 1, 0), jnp.moveaxis(dtx, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    h_fin, ys = jax.lax.scan(body, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Lp, nh, P)[:, :L]
    return y, h_fin.reshape(Bsz, nh, P, N)


def ssd_step(x, dt, a_neg, Bm, Cm, state):
    """Single-token SSD update.  x [B, nh, P]; dt [B, nh]; Bm/Cm [B, G, N];
    state [B, nh, P, N].  Returns (y [B, nh, P], new_state)."""
    Bsz, nh, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    hg = nh // G
    x = x.astype(jnp.float32).reshape(Bsz, G, hg, P)
    dt = dt.astype(jnp.float32).reshape(Bsz, G, hg)
    a = a_neg.reshape(G, hg)
    da = jnp.exp(dt * a)                                    # [B,G,hg]
    dtx = dt[..., None] * x
    upd = jnp.einsum("bgn,bghp->bghpn", Bm.astype(jnp.float32), dtx)
    st = state.reshape(Bsz, G, hg, P, N)
    st = da[..., None, None] * st + upd
    y = jnp.einsum("bgn,bghpn->bghp", Cm.astype(jnp.float32), st)
    return y.reshape(Bsz, nh, P), st.reshape(Bsz, nh, P, N)


def _ssd_pre(cfg, p, x):
    """Token-parallel in-projections.  x [..., d] ->
    (z, x_raw, B_raw, C_raw, dt_raw)."""
    return (x @ p["w_z"], x @ p["w_x"], x @ p["w_B"], x @ p["w_C"],
            x @ p["w_dt"])


def _ssd_conv3(cfg, p, x_raw, B_raw, C_raw, cache, valid_len=None):
    """Depthwise causal convs on x/B/C with per-component state caches.
    cache: dict with conv_x/conv_B/conv_C rows (or None for train)."""
    cx = cache["conv_x"] if cache is not None else None
    cb = cache["conv_B"] if cache is not None else None
    cc = cache["conv_C"] if cache is not None else None
    xo, ncx = _causal_conv(x_raw, cx, p["conv_x_w"], p["conv_x_b"], valid_len)
    bo, ncb = _causal_conv(B_raw, cb, p["conv_B_w"], p["conv_B_b"], valid_len)
    co, ncc = _causal_conv(C_raw, cc, p["conv_C_w"], p["conv_C_b"], valid_len)
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    xo = jax.nn.silu(xo)
    bo = jax.nn.silu(bo).reshape(*bo.shape[:-1], g, n)
    co = jax.nn.silu(co).reshape(*co.shape[:-1], g, n)
    return xo, bo, co, {"conv_x": ncx, "conv_B": ncb, "conv_C": ncc}


def _ssd_post(cfg, p, y, x_in, z, dt):
    """y [...,nh,P]: add skip, gated norm, out-proj."""
    y = y + p["d_skip"][..., :, None] * x_in.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], cfg.d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = cm.rms_norm(y.astype(z.dtype), p["norm_w"], 1e-5)
    return y @ p["w_out"]


def ssd_batched(cfg, p, x, cache, *, train: bool):
    Bsz, L, _ = x.shape
    nh, P = cfg.n_ssm_heads, cfg.ssm_headdim
    z, x_raw, B_raw, C_raw, dt_raw = _ssd_pre(cfg, p, x)
    use_cache = not (train or cache is None)
    xi, Bm, Cm, new_conv = _ssd_conv3(cfg, p, x_raw, B_raw, C_raw,
                                      cache if use_cache else None)
    xi = xi.reshape(Bsz, L, nh, P)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a_neg = -jnp.exp(p["a_log"])
    h0 = cache["state"] if use_cache else None
    y, h_fin = ssd_scan(xi, dt, a_neg, Bm, Cm, h0, cfg.ssm_chunk)
    out = _ssd_post(cfg, p, y, xi, z, dt)
    new_cache = cache if not use_cache else {"state": h_fin, **new_conv}
    return out, new_cache


def ssd_packed(cfg, p, x, cache, pk: PackedBatch):
    C, D = pk.num_chunk, pk.num_decode
    nh, P = cfg.n_ssm_heads, cfg.ssm_headdim
    z, x_raw, B_raw, C_raw, dt_raw = _ssd_pre(cfg, p, x)   # fused over [T]
    a_neg = -jnp.exp(p["a_log"])
    st_all = cache["state"]
    conv_all = {k: cache[k] for k in ("conv_x", "conv_B", "conv_C")}
    ys = []
    if C:
        row = lambda c: jax.lax.dynamic_index_in_dim(c, pk.chunk_slot, 0, True)
        h0 = row(st_all)
        xi, Bm, Cm, new_cs = _ssd_conv3(
            cfg, p, x_raw[None, :C], B_raw[None, :C], C_raw[None, :C],
            {k: row(v) for k, v in conv_all.items()}, valid_len=pk.chunk_len)
        xi = xi.reshape(1, C, nh, P)
        dt = jax.nn.softplus(dt_raw[None, :C].astype(jnp.float32)
                             + p["dt_bias"])
        # padded tokens: dt = 0 -> exp(0)*h + 0 (state passes through)
        dt = jnp.where((jnp.arange(C) < pk.chunk_len)[None, :, None],
                       dt, 0.0)
        y, h_fin = ssd_scan(xi, dt, a_neg, Bm, Cm, h0, cfg.ssm_chunk)
        st_all = jax.lax.dynamic_update_index_in_dim(
            st_all, h_fin[0], pk.chunk_slot, 0)
        conv_all = {k: jax.lax.dynamic_update_index_in_dim(
            conv_all[k], new_cs[k][0], pk.chunk_slot, 0) for k in conv_all}
        yc = _ssd_post(cfg, p, y[0], xi[0], z[:C], dt[0])
        ys.append(yc)
    if D:
        h0 = st_all[pk.decode_slots]
        xi, Bm, Cm, new_cs = _ssd_conv3(
            cfg, p, x_raw[C:, None], B_raw[C:, None], C_raw[C:, None],
            {k: v[pk.decode_slots] for k, v in conv_all.items()})
        xi = xi.reshape(D, nh, P)
        Bm, Cm = Bm[:, 0], Cm[:, 0]
        dt = jax.nn.softplus(dt_raw[C:].astype(jnp.float32) + p["dt_bias"])
        y, h_fin = ssd_step(xi, dt, a_neg, Bm, Cm, h0)
        st_all = st_all.at[pk.decode_slots].set(h_fin)
        conv_all = {k: conv_all[k].at[pk.decode_slots].set(new_cs[k])
                    for k in conv_all}
        yd = _ssd_post(cfg, p, y, xi, z[C:], dt)
        ys.append(yd)
    out = jnp.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
    return out, {"state": st_all, **conv_all}


# ==========================================================================
# MoE FFN (capacity-factor top-k dispatch, GShard-style but sort-free)
# ==========================================================================
# Sharding hint for the dispatch/capacity buffers (set by the launcher).
# Without it XLA materialises a REPLICATED [E, cap, d] scatter buffer and
# all-gathers the gathered token pairs (§Perf iterations 1-3): the fix is a
# shard-LOCAL dispatch — tokens reshape to [n_shards, T/n_shards, d], the
# position-in-expert cumsum and capacity buffer get a leading shard axis
# pinned to the data axis, and no dispatch collective remains (per-shard
# capacity semantics, as in production MoE systems).
_MOE_DISPATCH_SPEC = None
_MOE_DISPATCH_SHARDS = 1


def set_moe_dispatch_spec(spec, shards: int = 1):
    global _MOE_DISPATCH_SPEC, _MOE_DISPATCH_SHARDS
    _MOE_DISPATCH_SPEC = spec
    _MOE_DISPATCH_SHARDS = max(int(shards), 1)


def init_moe(cfg: ModelConfig, key, dtype) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": cm.dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": cm.dense_init(ks[1], (E, d, f), dtype),
        "w_up": cm.dense_init(ks[2], (E, d, f), dtype),
        "w_down": cm.dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.moe_shared_d_ff:
        p["shared"] = cm.init_glu_ffn(ks[4], d, cfg.moe_shared_d_ff, dtype)
    return p


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                    * cfg.capacity_factor)
    return max(4, -(-cap // 4) * 4)                        # round up to 4


def moe_ffn(cfg, p, x2d, act: str = "silu"):
    """x2d [T, d].  Returns (out [T, d], aux load-balance loss scalar).

    When a dispatch hint is set (distributed execution) the token axis is
    grouped into shards and dispatch is shard-local; otherwise single-group.
    """
    T, d = x2d.shape
    G = _MOE_DISPATCH_SHARDS
    if G > 1 and T % G == 0 and (T // G) >= cfg.top_k:
        xg = x2d.reshape(G, T // G, d)
        if _MOE_DISPATCH_SPEC is not None:
            xg = jax.lax.with_sharding_constraint(
                xg, jax.sharding.PartitionSpec("data", None, None))
        out, aux = _moe_grouped(cfg, p, xg, act)
        out = out.reshape(T, d)
    else:
        out, aux = _moe_grouped(cfg, p, x2d[None], act)
        out = out[0]
    if "shared" in p:
        out = out + cm.glu_ffn(p["shared"], x2d, act)
    return out, aux


def _moe_grouped(cfg, p, xg, act: str):
    """xg [G, t, d] — per-group (shard-local) capacity dispatch."""
    G, t, d = xg.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(cfg, t)
    spec = _MOE_DISPATCH_SPEC if G > 1 else None
    P = jax.sharding.PartitionSpec

    logits = (xg.astype(jnp.float32) @ p["router"])         # [G, t, E]
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1).astype(xg.dtype)   # [G, t, k]

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    e_flat = topi.reshape(G, t * k)                         # [G, tk]
    g_flat = gates.reshape(G, t * k)
    t_idx = jnp.tile(jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), (G, 1))
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)     # [G, tk, E]
    pos = jnp.sum((jnp.cumsum(onehot, axis=1) - 1) * onehot, axis=2)
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap).astype(jnp.int32)     # overflow slot

    slot = e_flat * (cap + 1) + pos_c                       # [G, tk] flat
    # vmapped per-shard scatter: the shard axis becomes a scatter BATCHING
    # dim, which the SPMD partitioner keeps local (a 2-D advanced-index
    # scatter is all-gathered instead — §Perf iteration 3)
    xpairs = jnp.take_along_axis(xg, t_idx[..., None], axis=1)  # [G, tk, d]

    def _dispatch_one(xp_s, slot_s):
        buf = jnp.zeros((E * (cap + 1), d), xg.dtype)
        return buf.at[slot_s].set(xp_s)

    bufflat = jax.vmap(_dispatch_one)(xpairs, slot)
    if spec is not None:
        bufflat = jax.lax.with_sharding_constraint(
            bufflat, P("data", None, None))
    xe = bufflat.reshape(G, E, cap + 1, d)[:, :, :cap]      # [G, E, cap, d]
    if spec is not None:
        # EP archs reshard [G(data), E, ...] -> [E(data), ...] via the
        # all-to-all XLA inserts for the expert einsum below
        xe = jax.lax.with_sharding_constraint(
            xe, P("data", None, None, None))
    afn = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
           "relu": jax.nn.relu}[act]
    h = afn(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])       # [G, E, cap, d]
    if spec is not None:
        ye = jax.lax.with_sharding_constraint(
            ye, P("data", None, None, None))
    ypad = jnp.concatenate([ye, jnp.zeros((G, E, 1, d), ye.dtype)],
                           axis=2).reshape(G, E * (cap + 1), d)
    y_tok = jnp.take_along_axis(ypad, slot[..., None], axis=1)  # [G, tk, d]
    w = (g_flat * keep.astype(g_flat.dtype))[..., None]

    def _combine_one(yt_s, ti_s):
        return jnp.zeros((t, d), xg.dtype).at[ti_s].add(yt_s)

    out = jax.vmap(_combine_one)(y_tok * w, t_idx)
    return out, aux
