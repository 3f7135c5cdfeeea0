"""Generic decoder stack: builds any configured architecture out of
``repro.models.blocks`` and exposes three entry points:

* ``forward_batched`` — x [B, L]: training (cache=None), batched prefill /
  chunked prefill (per-row ``start``), batched decode (L == 1);
* ``forward_packed`` — a SARATHI :class:`PackedBatch` (1 chunk + D decodes)
  with fused linear operators;
* ``encode`` — encoder pass for enc-dec models (bidirectional, no cache).

Layers are scanned in *groups* (the smallest repeating block pattern:
1 for homogeneous stacks, 3 for RecurrentGemma's 2:1 pattern, 5 for
Llama-3.2-Vision's cross-attention interleave) so the compiled HLO is O(1)
in depth; a non-divisible remainder becomes explicit tail layers.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as bk
from repro.models import common as cm
from repro.models.packed import PackedBatch


# --------------------------------------------------------------------------
# layer-kind pattern
# --------------------------------------------------------------------------
def layer_kinds(cfg: ModelConfig) -> List[str]:
    if cfg.family == "ssm":
        return ["ssd"] * cfg.n_layers
    if cfg.family == "encdec":
        return ["xdec"] * cfg.n_layers
    out = []
    for i in range(cfg.n_layers):
        k = cfg._layer_kind(i)
        if k == "dense":
            out.append("swa" if cfg.sliding_window else "dense")
        elif k == "moe":
            out.append("moe")
        elif k == "rglru":
            out.append("rglru")
        elif k == "local_attn":
            out.append("local")
        elif k == "cross_attn":
            out.append("cross")
        else:
            raise ValueError(k)
    return out


def stack_period(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return len(cfg.block_pattern)
    if cfg.family == "vlm" and cfg.cross_attn_every:
        return cfg.cross_attn_every
    return 1


def group_split(cfg: ModelConfig) -> Tuple[List[str], int, List[str]]:
    """-> (group_kinds, n_groups, tail_kinds)."""
    kinds = layer_kinds(cfg)
    p = stack_period(cfg)
    n_groups = cfg.n_layers // p
    return kinds[:p], n_groups, kinds[n_groups * p:]


# --------------------------------------------------------------------------
# single-layer init / apply  (norms + mixer + ffn)
# --------------------------------------------------------------------------
_ATTN_KINDS = ("dense", "moe", "enc")


def _ffn_spec(cfg: ModelConfig, kind: str) -> str:
    if kind == "ssd":
        return "none"
    if kind == "moe":
        return "moe"
    if kind in ("enc", "xdec") and cfg.act in ("relu", "gelu"):
        return "mlp"
    return "glu" if cfg.act in ("silu",) else "mlp"


def init_layer(cfg: ModelConfig, kind: str, key, dtype) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Dict = {"ln1": jnp.ones((d,), dtype)}
    if kind in ("dense", "swa", "local", "moe", "enc"):
        p["mixer"] = bk.init_attention(cfg, ks[0], dtype)
    elif kind == "cross":
        p["mixer"] = bk.init_attention(cfg, ks[0], dtype)
    elif kind == "rglru":
        p["mixer"] = bk.init_rglru(cfg, ks[0], dtype)
    elif kind == "ssd":
        p["mixer"] = bk.init_ssd(cfg, ks[0], dtype)
        return p                                   # ssd block has no ffn
    elif kind == "xdec":
        p["mixer"] = bk.init_attention(cfg, ks[0], dtype)
        p["lnc"] = jnp.ones((d,), dtype)
        p["cross"] = bk.init_attention(cfg, ks[3], dtype)
    else:
        raise ValueError(kind)
    p["ln2"] = jnp.ones((d,), dtype)
    fs = _ffn_spec(cfg, kind)
    if fs == "glu":
        p["ffn"] = cm.init_glu_ffn(ks[1], d, cfg.d_ff, dtype,
                                   bias=cfg.mlp_bias)
    elif fs == "mlp":
        p["ffn"] = cm.init_mlp_ffn(ks[1], d, cfg.d_ff, dtype)
    elif fs == "moe":
        p["ffn"] = bk.init_moe(cfg, ks[1], dtype)
    return p


def init_layer_cache(cfg: ModelConfig, kind: str, rows: int, max_len: int,
                     dtype, paged: Optional[Tuple[int, int]] = None) -> Dict:
    """``paged`` = (n_blocks, block_size) pools the full-attention KV of
    this layer (block-table indirection, see ``repro.cache``); window /
    cross / recurrent state stays slot-indexed — it is O(1) or O(window)
    per request, so paging buys nothing there."""
    def full_attn():
        if paged is not None:
            return bk.init_paged_attn_cache(cfg, paged[0], paged[1], dtype)
        return bk.init_attn_cache(cfg, rows, max_len, dtype)

    if kind in ("dense", "moe"):
        return {"attn": full_attn()}
    if kind == "swa":
        w = min(cfg.sliding_window, max_len)
        return {"attn": bk.init_swa_cache(cfg, rows, w, dtype)}
    if kind == "local":
        w = min(cfg.local_window, max_len)
        return {"attn": bk.init_swa_cache(cfg, rows, w, dtype)}
    if kind == "cross":
        return {"cross": bk.init_cross_cache(cfg, rows, dtype)}
    if kind == "rglru":
        return {"lru": bk.init_rglru_cache(cfg, rows, dtype)}
    if kind == "ssd":
        return {"ssd": bk.init_ssd_cache(cfg, rows, dtype)}
    if kind == "xdec":
        return {"attn": full_attn(),
                "cross": bk.init_cross_cache(cfg, rows, dtype)}
    if kind == "enc":
        return {}
    raise ValueError(kind)


def _apply_ffn(cfg, kind, p, x):
    """x [..., d] -> (out, aux)."""
    fs = _ffn_spec(cfg, kind)
    if fs == "none":
        return None, 0.0
    h = cm.rms_norm(x, p["ln2"], cfg.norm_eps)
    if fs == "glu":
        return cm.glu_ffn(p["ffn"], h, cfg.act), 0.0
    if fs == "mlp":
        return cm.mlp_ffn(p["ffn"], h, cfg.act), 0.0
    h2 = h.reshape(-1, cfg.d_model)
    out, aux = bk.moe_ffn(cfg, p["ffn"], h2, "silu")
    return out.reshape(x.shape), aux


def apply_layer_batched(cfg, kind, p, x, cache, start, *, train, memory):
    h = cm.rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = dict(cache) if cache else cache
    if kind in ("dense", "moe"):
        mo, c = bk.attn_batched(cfg, p["mixer"], h, cache and cache["attn"],
                                start, train=train)
        if cache:
            new_cache["attn"] = c
    elif kind in ("swa", "local"):
        w = cfg.sliding_window if kind == "swa" else cfg.local_window
        mo, c = bk.attn_batched(cfg, p["mixer"], h, cache and cache["attn"],
                                start, train=train, window=w)
        if cache:
            new_cache["attn"] = c
    elif kind == "enc":
        mo, _ = bk.attn_batched(cfg, p["mixer"], h, None, start,
                                train=True, causal=False)
    elif kind == "cross":
        mo, c = bk.cross_batched(cfg, p["mixer"], h,
                                 cache and cache["cross"], memory=memory)
        if cache:
            new_cache["cross"] = c
    elif kind == "rglru":
        mo, c = bk.rglru_batched(cfg, p["mixer"], h,
                                 cache and cache["lru"], train=train)
        if cache:
            new_cache["lru"] = c
    elif kind == "ssd":
        mo, c = bk.ssd_batched(cfg, p["mixer"], h,
                               cache and cache["ssd"], train=train)
        if cache:
            new_cache["ssd"] = c
        return x + mo, new_cache, 0.0
    elif kind == "xdec":
        mo, c = bk.attn_batched(cfg, p["mixer"], h, cache and cache["attn"],
                                start, train=train)
        if cache:
            new_cache["attn"] = c
        x = x + mo
        hc = cm.rms_norm(x, p["lnc"], cfg.norm_eps)
        mo, cc = bk.cross_batched(cfg, p["cross"], hc,
                                  cache and cache["cross"], memory=memory)
        if cache:
            new_cache["cross"] = cc
    else:
        raise ValueError(kind)
    x = x + mo
    fo, aux = _apply_ffn(cfg, kind, p, x)
    if fo is not None:
        x = x + fo
    return x, new_cache, aux


def apply_layer_packed(cfg, kind, p, x, cache, pk: PackedBatch):
    h = cm.rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = dict(cache)
    if kind in ("dense", "moe"):
        mo, new_cache["attn"] = bk.attn_packed(cfg, p["mixer"], h,
                                               cache["attn"], pk)
    elif kind in ("swa", "local"):
        w = cfg.sliding_window if kind == "swa" else cfg.local_window
        mo, new_cache["attn"] = bk.attn_packed(cfg, p["mixer"], h,
                                               cache["attn"], pk, window=w)
    elif kind == "cross":
        mo, new_cache["cross"] = bk.cross_packed(cfg, p["mixer"], h,
                                                 cache["cross"], pk)
    elif kind == "rglru":
        mo, new_cache["lru"] = bk.rglru_packed(cfg, p["mixer"], h,
                                               cache["lru"], pk)
    elif kind == "ssd":
        mo, new_cache["ssd"] = bk.ssd_packed(cfg, p["mixer"], h,
                                             cache["ssd"], pk)
        return _sp_scatter(x + mo), new_cache, 0.0
    elif kind == "xdec":
        mo, new_cache["attn"] = bk.attn_packed(cfg, p["mixer"], h,
                                               cache["attn"], pk)
        x = _sp_scatter(x + mo)
        hc = cm.rms_norm(x, p["lnc"], cfg.norm_eps)
        mo, new_cache["cross"] = bk.cross_packed(cfg, p["cross"], hc,
                                                 cache["cross"], pk)
    else:
        raise ValueError(kind)
    # SP: each residual add is pinned token-sharded — this is where the
    # row-parallel matmul's all-reduce splits into reduce-scatter (here) +
    # all-gather (in front of the next sharded matmul, inserted by GSPMD)
    x = _sp_scatter(x + mo)
    with jax.named_scope("ffn"):
        fo, aux = _apply_ffn(cfg, kind, p, x)
    if fo is not None:
        x = _sp_scatter(x + fo)
    return x, new_cache, aux


# --------------------------------------------------------------------------
# full-stack init
# --------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict:
    group_kinds, n_groups, tail_kinds = group_split(cfg)
    keys = jax.random.split(key, 8)
    params: Dict = {
        "embed": cm.embed_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = cm.dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), dtype)

    def make_group(k):
        lk = jax.random.split(k, len(group_kinds))
        return [init_layer(cfg, kind, lk[j], dtype)
                for j, kind in enumerate(group_kinds)]

    gkeys = jax.random.split(keys[2], max(n_groups, 1))
    groups = [make_group(gkeys[g]) for g in range(n_groups)]
    params["groups"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)

    tkeys = jax.random.split(keys[3], max(len(tail_kinds), 1))
    params["tail"] = [init_layer(cfg, kind, tkeys[j], dtype)
                      for j, kind in enumerate(tail_kinds)]

    if cfg.n_encoder_layers:
        ekeys = jax.random.split(keys[4], cfg.n_encoder_layers)
        enc = [init_layer(cfg, "enc", ekeys[i], dtype)
               for i in range(cfg.n_encoder_layers)]
        params["encoder"] = {
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
    return params


def init_cache(cfg: ModelConfig, rows: int, max_len: int,
               dtype=jnp.float32, *, paged_blocks: Optional[int] = None,
               block_size: Optional[int] = None) -> Dict:
    """``paged_blocks``/``block_size`` switch full-attention KV to the
    pooled paged layout (every layer gets its own ``paged_blocks``-block
    pool; one block table per request addresses all layers)."""
    group_kinds, n_groups, tail_kinds = group_split(cfg)
    paged = None
    if paged_blocks is not None:
        if not block_size:
            raise ValueError("paged cache needs block_size")
        paged = (int(paged_blocks), int(block_size))

    # every group starts from the same state: broadcast one group instead
    # of stacking n_groups copies (which holds the copies and the stack at
    # once — twice the KV pool in device memory)
    group = [init_layer_cache(cfg, kind, rows, max_len, dtype, paged)
             for kind in group_kinds]
    return {
        "groups": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), group),
        "tail": [init_layer_cache(cfg, kind, rows, max_len, dtype, paged)
                 for kind in tail_kinds],
    }


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------
def _unembed(cfg, params, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["unembed"]


from repro import env

# Optional sequence-parallel sharding constraint applied to the residual
# stream at every group boundary in TRAIN mode (Megatron sequence
# parallelism).  The per-group remat stash is then stored sharded over the
# model axis — without this, a 48-layer 5120-wide model's [G, B, S, d]
# residual stash alone exceeds per-chip HBM.  Set by the launcher.
_TRAIN_ACT_SPEC = None
_CACHE_ACT_SPEC = None


def set_train_activation_spec(spec):
    """spec: jax.sharding.PartitionSpec for [B, S, d] activations (None to
    disable)."""
    global _TRAIN_ACT_SPEC
    _TRAIN_ACT_SPEC = spec


def set_cache_activation_spec(spec):
    """Layer-boundary activation constraint for cache-mode (serve) steps.
    §Perf: FSDP-sharded archs decode ONE token per sequence — re-sharding
    the (tiny) activations onto the weight shards makes the per-layer
    collectives O(activations) instead of an O(weights) all-gather."""
    global _CACHE_ACT_SPEC
    _CACHE_ACT_SPEC = spec


def _constrain_cache_act(x):
    """Apply ``_CACHE_ACT_SPEC`` only when its rank matches ``x``: the
    launch stack sets it for batched ``[B, S, d]`` serve steps, while the
    TP engines' packed path carries rank-2 ``[T, d]`` activations through
    the same group scan (GSPMD lays those out from the param shardings
    alone) — a rank-mismatched constraint must be a no-op, not an error."""
    if _CACHE_ACT_SPEC is None or len(_CACHE_ACT_SPEC) != x.ndim:
        return x
    return jax.lax.with_sharding_constraint(x, _CACHE_ACT_SPEC)


# Sequence parallelism over the packed token axis (Megatron SP on the
# serving engines' packed path).  When set to a NamedSharding with spec
# ``P("model", None)``, every residual add in ``apply_layer_packed`` pins
# the ``[T, d]`` residual stream token-sharded — GSPMD then lowers each
# row-parallel matmul's partial-sum combine to a reduce-scatter (instead
# of an all-reduce) and inserts the matching all-gather just before the
# next column-parallel matmul, so RMSNorm + residual adds run on T/tp
# tokens per chip at identical communication volume.  ``None`` (the
# default, and tp=1) keeps the trace byte-for-byte untouched.  Set by the
# engines right before each jitted packed step, mirroring
# ``bk.set_paged_attn_mesh``; it is a NamedSharding because the engine
# jits do not run inside a ``with mesh:`` context.
_PACKED_SP_SHARDING = None


def set_packed_sp_sharding(sharding):
    """sharding: ``jax.sharding.NamedSharding`` over the packed token axis
    (see :func:`repro.sharding.placement.sp_activation_sharding`), or
    ``None`` to disable.  The packed token count must already be a
    multiple of the mesh's model-axis size
    (:func:`repro.sharding.placement.pad_tokens_to_tp`)."""
    global _PACKED_SP_SHARDING
    _PACKED_SP_SHARDING = sharding


def _sp_scatter(x):
    """Pin a packed ``[T, d]`` residual to the SP token-sharded layout
    (the reduce-scatter side of the RS/AG pair); identity when SP is off
    or ``x`` is not the rank-2 packed residual."""
    if _PACKED_SP_SHARDING is None or x.ndim != 2:
        return x
    return jax.lax.with_sharding_constraint(x, _PACKED_SP_SHARDING)


def _sp_gather(x):
    """Pin a packed ``[T, d]`` residual back to fully-replicated (the
    all-gather side) — used once on the last stage before the final norm /
    logits glue, whose dynamic chunk-row slice must see every token."""
    if _PACKED_SP_SHARDING is None or x.ndim != 2:
        return x
    import jax.sharding as _shd
    rep = _shd.NamedSharding(_PACKED_SP_SHARDING.mesh, _shd.PartitionSpec())
    return jax.lax.with_sharding_constraint(x, rep)


def _scan_unroll() -> int | bool:
    """REPRO_SCAN_UNROLL=1 fully unrolls the layer scan — used by the
    roofline pass so compiled.cost_analysis() counts every layer (XLA does
    not multiply loop bodies by trip count)."""
    return env.get("REPRO_SCAN_UNROLL")


def _scan_cached_groups(group_kinds, apply_fn, x, aux, params_groups,
                        cache_groups, *, remat: bool = False):
    """Scan the grouped layers with the stacked cache in the carry.

    Each layer takes its cache from the stacked leaves through
    :func:`~repro.models.blocks.carry_in` and puts it back through
    :func:`~repro.models.blocks.carry_out`: the paged pool leaf never
    leaves the stack (the layer reads and writes whole blocks at its
    block offset in it, and the returned stack goes straight back into
    the carry); every other leaf (dense ``k``/``v``, window rings,
    recurrent and SSD state) is sliced with ``dynamic_index_in_dim`` and
    written back with ``dynamic_update_index_in_dim``, which the TPU
    compiler may copy and re-lay out.  Scanning the cache as xs -> ys
    instead allocates the outputs as a second whole cache (a second KV
    pool's worth of device memory).  Returns (x, aux, new_cache_groups)."""
    n = jax.tree.leaves(params_groups)[0].shape[0]

    def group_body(carry, xs):
        x, aux, gcs = carry
        gp, i = xs
        x = _constrain_cache_act(x)
        with jax.named_scope("kv_carry"):
            gc = jax.tree_util.tree_map_with_path(
                lambda path, a: bk.carry_in(path, a, i), gcs)
        new_gc = []
        for j, kind in enumerate(group_kinds):
            x, nc, a = apply_fn(kind, gp[j], gc[j], x)
            new_gc.append(nc)
            aux = aux + a
        with jax.named_scope("kv_carry"):
            gcs = jax.tree.map(lambda a, u: bk.carry_out(a, u, i),
                               gcs, new_gc)
        return (x, aux, gcs), None

    body = jax.checkpoint(group_body, prevent_cse=False) if remat else group_body
    (x, aux, new_groups), _ = jax.lax.scan(
        body, (x, aux, cache_groups),
        (params_groups, jnp.arange(n, dtype=jnp.int32)),
        unroll=_scan_unroll())
    return x, aux, new_groups


def _run_layers(cfg, params, cache, x, apply_fn, remat: bool):
    """Scan the grouped layers then the tail.  ``apply_fn(kind, p, c, x)``
    -> (x, new_c, aux)."""
    group_kinds, n_groups, tail_kinds = group_split(cfg)
    has_cache = cache is not None
    unroll = _scan_unroll()

    if has_cache:
        x, aux, new_groups = _scan_cached_groups(
            group_kinds, apply_fn, x, jnp.float32(0.0), params["groups"],
            cache["groups"], remat=remat)
        new_tail = []
        for j, kind in enumerate(tail_kinds):
            x, nc, a = apply_fn(kind, params["tail"][j], cache["tail"][j], x)
            new_tail.append(nc)
            aux = aux + a
        return x, {"groups": new_groups, "tail": new_tail}, aux

    def group_body_nc(carry, gp):
        x, aux = carry
        if _TRAIN_ACT_SPEC is not None:
            # sequence-parallel boundary: the remat stash saves x SHARDED
            x = jax.lax.with_sharding_constraint(x, _TRAIN_ACT_SPEC)
        for j, kind in enumerate(group_kinds):
            x, _, a = apply_fn(kind, gp[j], None, x)
            aux = aux + a
        return (x, aux), 0

    body = jax.checkpoint(group_body_nc, prevent_cse=False) if remat else group_body_nc
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["groups"],
                               unroll=unroll)
    for j, kind in enumerate(tail_kinds):
        x, _, a = apply_fn(kind, params["tail"][j], None, x)
        aux = aux + a
    return x, None, aux


def forward_batched(cfg: ModelConfig, params, tokens, cache=None, start=None,
                    *, memory=None, train: bool = False,
                    logits_mode: str = "all", remat: bool = False):
    """tokens [B, L] int32.  Returns (logits, new_cache, aux).

    ``logits_mode``: "all" -> [B, L, V]; "last" -> [B, V]; "none" -> None.
    """
    B, L = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if start is None:
        start = jnp.zeros((B,), jnp.int32)

    def apply_fn(kind, p, c, x):
        return apply_layer_batched(cfg, kind, p, x, c, start,
                                   train=train, memory=memory)

    x, new_cache, aux = _run_layers(cfg, params, cache, x, apply_fn, remat)
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_mode == "all":
        logits = _unembed(cfg, params, x)
    elif logits_mode == "last":
        logits = _unembed(cfg, params, x[:, -1])
    elif logits_mode == "hidden":
        logits = x                       # final hidden states, no unembed
    else:
        logits = None
    return logits, new_cache, aux


def forward_packed(cfg: ModelConfig, params, pk: PackedBatch, cache):
    """SARATHI hybrid step.  Returns (chunk_logits [1,V] | None,
    decode_logits [D,V] | None, new_cache, aux).

    The monolithic forward IS the one-stage pipeline: it delegates to
    :func:`forward_packed_stage` with ``first=last=True``, so there is a
    single copy of the cached layer-scan + logits code and the pp-stage
    composition is bit-identical by construction."""
    (chunk_logits, decode_logits), new_cache, aux = forward_packed_stage(
        cfg, params, pk, cache, None, first=True, last=True)
    return chunk_logits, decode_logits, new_cache, aux


def forward_packed_stage(cfg: ModelConfig, params, pk: PackedBatch, cache,
                         x, *, first: bool, last: bool):
    """One pipeline-parallel stage of :func:`forward_packed`.

    ``params`` / ``cache`` hold a contiguous slice of the grouped layers
    (plus the embedding on the first stage and the tail layers / final norm
    / unembedding on the last — see ``repro.launch.pipeline``).  The first
    stage embeds ``pk``'s tokens and ignores ``x``; interior stages take
    and return the ``[T, d]`` residual stream; the last stage returns
    ``(chunk_logits, decode_logits)`` exactly like :func:`forward_packed`.

    Composing the stages in order is BIT-identical to the monolithic
    forward: the group scan is sliced, not altered — every per-layer
    computation is byte-for-byte the one :func:`_run_layers` runs, and the
    residual carry crosses stage boundaries unchanged
    (tests/test_stage_partition.py pins this exactly).
    """
    group_kinds, _, tail_kinds = group_split(cfg)
    if first:
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], pk.token_ids(), axis=0)
    x = _sp_scatter(x)      # SP entry: shard the residual carry up front

    def apply_fn(kind, p, c, x):
        return apply_layer_packed(cfg, kind, p, x, c, pk)

    aux = jnp.float32(0.0)
    new_cache = {}
    if "groups" in cache:
        x, aux, new_cache["groups"] = _scan_cached_groups(
            group_kinds, apply_fn, x, aux, params["groups"], cache["groups"])
    if "tail" in cache:
        new_tail = []
        for j, kind in enumerate(tail_kinds):
            tc = cache["tail"][j]
            x, nc, a = apply_fn(
                kind, params["tail"][j],
                jax.tree_util.tree_map_with_path(bk.carry_in, tc), x)
            new_tail.append(jax.tree.map(bk.carry_out, tc, nc))
            aux = aux + a
        new_cache["tail"] = new_tail
    if not last:
        return x, new_cache, aux

    # SP exit: the final all-gather — the dynamic chunk-row slice and the
    # decode-lane split below index arbitrary token rows
    x = _sp_gather(x)
    C, D = pk.num_chunk, pk.num_decode
    with jax.named_scope("unembed"):
        x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if C:
            # last *valid* chunk row (the chunk may be padded past
            # chunk_len)
            last_row = jax.lax.dynamic_slice_in_dim(
                x, jnp.maximum(pk.chunk_len - 1, 0), 1, axis=0)
            chunk_logits = _unembed(cfg, params, last_row)
        else:
            chunk_logits = None
        decode_logits = _unembed(cfg, params, x[C:]) if D else None
    return (chunk_logits, decode_logits), new_cache, aux


def encode(cfg: ModelConfig, params, frontend_embeds):
    """Bidirectional encoder over stub frontend embeddings [B, F, d]."""
    enc = params["encoder"]
    B = frontend_embeds.shape[0]
    start = jnp.zeros((B,), jnp.int32)
    x = frontend_embeds

    def body(x, lp):
        x, _, _ = apply_layer_batched(cfg, "enc", lp, x, None, start,
                                      train=True, memory=None)
        return x, 0

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return cm.rms_norm(x, enc["final_norm"], cfg.norm_eps)


def seed_cross_kv(cfg: ModelConfig, params, cache, memory, slot):
    """Compute per-layer cross-attention KV from ``memory`` [F, d] and write
    them into cache row ``slot`` (engine calls this when a VLM / enc-dec
    request enters the batch)."""
    group_kinds, n_groups, tail_kinds = group_split(cfg)

    def update_layer(kind, lp, lc):
        if kind == "cross":
            cp = lp["mixer"]
        elif kind == "xdec":
            cp = lp["cross"]
        else:
            return lc
        k, v = bk.compute_cross_kv(cfg, cp, memory)
        lc = dict(lc)
        lc["cross"] = {
            "ck": jax.lax.dynamic_update_index_in_dim(
                lc["cross"]["ck"], k.astype(lc["cross"]["ck"].dtype), slot, 0),
            "cv": jax.lax.dynamic_update_index_in_dim(
                lc["cross"]["cv"], v.astype(lc["cross"]["cv"].dtype), slot, 0),
        }
        return lc

    new_groups = []
    for j, kind in enumerate(group_kinds):
        if kind in ("cross", "xdec"):
            def upd(lp_g, lc_g, _kind=kind):
                return update_layer(_kind, lp_g, lc_g)
            new_groups.append(jax.vmap(upd)(params["groups"][j],
                                            cache["groups"][j]))
        else:
            new_groups.append(cache["groups"][j])
    new_tail = [update_layer(kind, params["tail"][j], cache["tail"][j])
                for j, kind in enumerate(tail_kinds)]
    return {"groups": new_groups, "tail": new_tail}
