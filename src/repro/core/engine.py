"""The SARATHI inference engine.

Owns the model parameters, the slot-indexed caches, and ONE jit-compiled
packed step of static shape ``(C, D)`` (C = chunk size, D = decode slots).
Every kind of engine iteration — pure chunked prefill, pure decode batch, or
a decode-maximal hybrid — is the same compiled computation:

* an iteration WITHOUT a prefill chunk runs a decode-only ``(0, D)``
  specialisation of the same step function (jit re-specialises on the packed
  shape): pure-decode iterations skip the C-wide scratch matmuls entirely
  instead of paying for a masked-out chunk lane.  ``warmup`` compiles both
  shapes;
* an iteration with fewer than D decodes pads the decode list with scratch
  rows;
* a final partial chunk of a prompt is padded to C with ``chunk_len`` masking
  (see repro.models.packed.PackedBatch).

This is how the paper's uniform-compute property is realised operationally:
every iteration is the *same shape* of work, so pipeline micro-batches are
balanced by construction.

With ``paged=True`` the full-attention KV moves from dense per-slot rows to
a block pool (``repro.cache``): the engine allocates blocks lazily per
chunk / decode step from a :class:`~repro.cache.BlockManager` (shareable
with a block-aware scheduler), threads per-request block tables through the
:class:`~repro.models.packed.PackedBatch`, and frees blocks on release —
including preemptive release for recompute when the pool runs dry.  Slots
remain for the O(1)-per-request state (ring windows, SSM/LRU, cross KV);
the old ``n_slots + 1`` scratch *row* survives only for those leaves, while
the paged KV's padding writes land in the reserved scratch *block*.

With ``tp > 1`` the engine is tensor-parallel: params and cache (dense and
paged leaves alike) are placed on a ``(1, tp)`` ``("data", "model")`` mesh
under the shared :mod:`repro.sharding` policy — the same leaf rules the
launch stack lowers against — and the jitted packed step SPMD-partitions
over the ``model`` axis from its argument shardings alone.  ``tp=1`` takes
the exact unsharded single-device path (bit-identity with prior releases
is pinned by tests); ``tp>1`` is equivalent only to tolerance tier: TP
all-reduces legitimately reorder float accumulation (see README §TPxPP).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cache import BlockManager
from repro.configs.base import ModelConfig
from repro.core.sampling import SamplingParams, sample
from repro.models import PackedBatch, build_model
from repro.models.blocks import POOL_KEY
from repro.models.registry import Model

# paged block-pool leaves (repro.models.blocks.init_paged_attn_cache) are
# block-indexed, not slot-indexed: nothing to wipe on slot reuse — freed
# blocks self-heal exactly like dense KV rows (overwritten before visible,
# or hidden by the context mask)
_POOL_KEYS = frozenset({POOL_KEY})


def _leaf_kind(path):
    """-> (lead, is_pool) for a cache-tree leaf path: ``lead`` is 1 when
    the leaf carries the scanned-group leading axis, and pool leaves are
    the block-indexed fused paged KV (``pkv``)."""
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    lead = 1 if "groups" in keys else 0
    return lead, bool(keys and keys[-1] in _POOL_KEYS)


def _extract_state(cache, slot, table):
    """Pull one request's cache state out of ``cache``: slot-indexed
    leaves yield their row ``slot``; pool leaves yield the request's
    block contents gathered through ``table`` (never the reserved scratch
    block — the table only ever lists allocated blocks).  The result has
    the cache's own tree structure with the slot (or block) axis replaced
    by the request's payload, so it round-trips through
    :func:`_install_state` on any engine with the same layout."""
    tbl = jnp.asarray(table, jnp.int32)

    def pick(path, leaf):
        lead, is_pool = _leaf_kind(path)
        if is_pool:
            return jnp.take(leaf, tbl, axis=lead)
        return leaf[(slice(None),) * lead + (slot,)]

    return jax.tree_util.tree_map_with_path(pick, cache)


def _install_state(cache, state, slot, table):
    """Inverse of :func:`_extract_state`: write the payload's rows into
    row ``slot`` of every slot-indexed leaf and scatter the pool payload
    into the destination blocks listed by ``table`` (the receiving
    engine's own allocation — block tables are REMAPPED, not copied)."""
    tbl = jnp.asarray(table, jnp.int32)

    def put(path, leaf, row):
        lead, is_pool = _leaf_kind(path)
        leaf = jnp.asarray(leaf)             # host-built trees lack .at
        row = jnp.asarray(row, leaf.dtype)
        if is_pool:
            return leaf.at[(slice(None),) * lead + (tbl,)].set(row)
        return leaf.at[(slice(None),) * lead + (slot,)].set(row)

    return jax.tree_util.tree_map_with_path(put, cache, state)


@dataclass
class KVHandoff:
    """One request's extracted cache state, in transit between engines
    (DistServe-style prefill->decode disaggregation, README §Disaggregated
    serving).  ``state`` is a host-side pytree in the MONOLITHIC cache
    structure — pipeline engines reassemble their stage slices into this
    canonical form on extract and re-slice on install, so the handoff
    composes across replicas of unequal ``pp``/``tp``.  The transfer is a
    pure cache relocation: under greedy sampling the receiving engine's
    token stream is bit-identical to never having moved."""
    state: object                # pytree: slot rows + gathered pool blocks
    n_blocks: int                # pool blocks in the payload (0 = dense)
    block_size: int              # source pool geometry (0 = dense)


def _copy_blocks(cache, src, dst):
    """Copy pool-block contents ``src[i] -> dst[i]`` on every paged KV
    leaf (slot-indexed leaves pass through).  This is the device half of a
    copy-on-write fork: the :class:`~repro.cache.BlockManager` swaps a
    shared block out of the writer's table for a fresh one, and this copy
    makes the fork hold the same KV before the write lands."""
    def cp(path, leaf):
        lead, is_pool = _leaf_kind(path)
        if not is_pool:
            return leaf
        rows = leaf[(slice(None),) * lead + (src,)]
        return leaf.at[(slice(None),) * lead + (dst,)].set(rows)

    return jax.tree_util.tree_map_with_path(cp, cache)


def _pad_pairs(pairs):
    """(src, dst) int32 arrays for :func:`_copy_blocks`, padded to a power
    of two with scratch->scratch no-op copies so the jitted copy only ever
    compiles O(log) distinct shapes."""
    n = 1
    while n < len(pairs):
        n *= 2
    src = np.zeros((n,), np.int32)
    dst = np.zeros((n,), np.int32)
    for i, (s, d) in enumerate(pairs):
        src[i], dst[i] = s, d
    return jnp.asarray(src), jnp.asarray(dst)


def _gather_pool(cache, idx):
    """Pull pool-block rows ``idx`` off every paged KV leaf (the device
    half of a swap-OUT).  Non-pool leaves contribute zero-size stand-ins
    so the result keeps the cache's tree structure — the host-arena
    helpers walk both trees together."""
    def pick(path, leaf):
        lead, is_pool = _leaf_kind(path)
        if is_pool:
            return jnp.take(leaf, idx, axis=lead)
        return jnp.zeros((0,), leaf.dtype)

    return jax.tree_util.tree_map_with_path(pick, cache)


def _scatter_pool(cache, rows, idx):
    """Write gathered pool rows back into blocks ``idx`` (the device half
    of a swap-IN; inverse of :func:`_gather_pool`).  Padded entries target
    the reserved scratch block, same as copy-on-write padding."""
    def put(path, leaf, row):
        lead, is_pool = _leaf_kind(path)
        if not is_pool:
            return leaf
        row = jnp.asarray(row, leaf.dtype)
        return leaf.at[(slice(None),) * lead + (idx,)].set(row)

    return jax.tree_util.tree_map_with_path(put, cache, rows)


def _reset_slot(cache, slot):
    """Zero every slot-indexed cache leaf's row ``slot`` (-1 for integer
    leaves, which are ring-buffer position markers where -1 == empty).

    The tree structure is derived from the cache dict itself rather than
    hard-coded: any leaf under a ``groups`` key carries a leading group
    axis before the slot axis (the scanned-layer stacking of
    ``repro.models.stack.init_cache``), block-pool leaves are skipped, and
    every other leaf is slot-major — so new cache shapes are wiped (or
    deliberately skipped) without this function having to know about them.
    """
    def wipe(path, leaf):
        lead, is_pool = _leaf_kind(path)
        if is_pool:
            return leaf
        fill = -1 if jnp.issubdtype(leaf.dtype, jnp.integer) else 0
        row = jnp.full(leaf.shape[:lead] + leaf.shape[lead + 1:], fill,
                       leaf.dtype)
        idx = (slice(None),) * lead + (slot,)
        return leaf.at[idx].set(row)

    return jax.tree_util.tree_map_with_path(wipe, cache)


@dataclass
class ChunkWork:
    req_id: int
    tokens: Sequence[int]       # the chunk's token ids (len <= C)
    start: int                  # tokens already prefilled
    is_last: bool               # final chunk -> sample the first output token


@dataclass
class DecodeWork:
    req_id: int
    token: int                  # last generated (or last prompt) token
    ctx: int                    # current context length


class IterationPlan:
    """One engine iteration, as constructed by a scheduler policy.

    Historically a plan carried at most ONE prefill chunk (SARATHI's
    decode-maximal batch).  Token-budget policies (Sarathi-Serve style) may
    pack SEVERAL chunks from different requests into one iteration, so the
    plan now holds a ``chunks`` list; ``chunk`` remains the single-chunk
    view used by the original policies and the packed engine step.
    """

    def __init__(self, chunk: Optional[ChunkWork] = None,
                 decodes: Optional[List[DecodeWork]] = None,
                 chunks: Optional[Sequence[ChunkWork]] = None):
        if chunk is not None and chunks:
            raise ValueError("pass either chunk= or chunks=, not both")
        self.chunks: List[ChunkWork] = (
            list(chunks) if chunks else ([chunk] if chunk is not None else []))
        self.decodes: List[DecodeWork] = list(decodes) if decodes else []

    @property
    def chunk(self) -> Optional[ChunkWork]:
        """The plan's first (for the original policies: only) chunk."""
        return self.chunks[0] if self.chunks else None

    @chunk.setter
    def chunk(self, work: Optional[ChunkWork]):
        self.chunks = [work] if work is not None else []

    @property
    def n_prefill_tokens(self) -> int:
        return sum(len(c.tokens) for c in self.chunks)

    @property
    def n_decode_tokens(self) -> int:
        return len(self.decodes)

    def __repr__(self) -> str:                       # pragma: no cover
        return (f"IterationPlan(chunks={self.chunks!r}, "
                f"decodes={self.decodes!r})")


class Engine:
    """Slot-based SARATHI execution engine.  ``tp`` tensor-parallel chips
    (``devices``, default the first local ones) shard params/cache under
    the launch stack's sharding policy (:mod:`repro.sharding`); ``tp=1``
    is the unsharded single-device path, bit-for-bit."""

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 max_len: int, chunk_size: int, decode_slots: int,
                 dtype=jnp.float32,
                 sampling: SamplingParams = SamplingParams(),
                 seed: int = 0, paged: bool = False,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 watermark: float = 0.0, host_blocks: int = 0,
                 block_manager: Optional[BlockManager] = None,
                 tp: int = 1, devices: Optional[Sequence] = None,
                 sp: bool = False):
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.params = params
        self.dtype = dtype
        self.C = int(chunk_size)
        self.D = int(decode_slots)
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.scratch = n_slots                    # extra scratch row
        self.block_manager: Optional[BlockManager] = None
        if paged or block_manager is not None:
            bm = block_manager
            if bm is None:
                if max_len % block_size:
                    raise ValueError(f"max_len={max_len} must be a "
                                     f"multiple of block_size={block_size}")
                if n_blocks is None:
                    # same token capacity as the dense rows it replaces,
                    # minus the max_len-long scratch row (now ONE block)
                    n_blocks = n_slots * (max_len // block_size) + 1
                bm = BlockManager(n_blocks, block_size,
                                  watermark=watermark,
                                  host_blocks=host_blocks)
            if max_len % bm.block_size:
                raise ValueError("max_len must tile by the block size")
            self.block_manager = bm
            self.blocks_per_seq = max_len // bm.block_size
            self.cache = self.model.init_cache(
                n_slots + 1, max_len, dtype, paged_blocks=bm.n_blocks,
                block_size=bm.block_size)
        else:
            self.blocks_per_seq = 0
            self.cache = self.model.init_cache(n_slots + 1, max_len, dtype)
        self.tp = int(tp)
        if self.tp > 1:
            from repro import sharding as shd
            shd.check_tp_supported(self.tp, self.paged, cfg)
            self.tp_mesh = shd.make_tp_mesh(self.tp, devices)
            self.params = shd.shard_params(cfg, self.params, self.tp_mesh)
            self.cache = shd.shard_cache(cfg, self.cache, self.tp_mesh)
        else:
            self.tp_mesh = None
            if devices:
                # placement-only (no sharding, no numeric effect): honour
                # an explicit device request instead of dropping it
                self.params = jax.device_put(self.params, devices[0])
                self.cache = jax.device_put(self.cache, devices[0])
        self._init_sp(sp, self.tp_mesh)
        self.sampling = sampling
        self._key = jax.random.PRNGKey(seed)
        self._free: List[int] = list(range(n_slots))
        self._slot_of: Dict[int, int] = {}
        # cache (arg 2) is donated: the KV/state buffers update in place
        self._step = jax.jit(self._step_impl, donate_argnums=(2,))
        self._seed_cross = jax.jit(self.model.seed_cross_kv)
        # donated too: the block pool passes through untouched, and without
        # donation every admission would copy the whole pool
        self._reset_slot = jax.jit(_reset_slot, donate_argnums=(0,))
        self._cow_blocks = jax.jit(_copy_blocks, donate_argnums=(0,))
        # host KV swap tier: the numpy arena mirroring the pool leaves is
        # built lazily on the first swap (shape [.., n_host_slots, ..] per
        # pool leaf); gather/scatter are jitted with the same power-of-two
        # padding as copy-on-write, so they compile O(log) shapes
        self._host_pool = None
        self._gather_pool = jax.jit(_gather_pool)
        self._scatter_pool = jax.jit(_scatter_pool, donate_argnums=(0,))
        self.iterations = 0
        # [1, V] logits of the last packed step's final valid chunk row
        # (what the sampler saw; None after a decode-only step) — left on
        # device, read only by correctness checks against a plain forward
        self.chunk_logits = None

    def _init_sp(self, sp: bool, mesh):
        """Resolve the sequence-parallel configuration: the activation
        sharding hint for the packed steps and the padded lane widths.

        SP pads the packed lane widths up to multiples of ``tp`` so the
        token axis splits evenly (``shd.pad_tokens_to_tp``): extra chunk
        rows sit past ``chunk_len`` (masked like any partial chunk) and
        extra decode lanes target the scratch slot (masked like any unused
        lane), so ragged batches stay correct.  ``self.C``/``self.D``
        remain the scheduler-visible budgets; only the compiled shapes
        grow.  With ``sp`` off or ``tp == 1`` the lanes equal the budgets
        and the hint is ``None`` — the trace is byte-for-byte the
        unsharded one.  The pipeline engine re-invokes this after it
        learns its per-stage tp (its base-class init runs at ``tp=1``)."""
        from repro import sharding as shd
        self.sp = bool(sp) and self.tp > 1
        self._sp_sharding = (shd.sp_activation_sharding(mesh)
                             if self.sp else None)
        if self._sp_sharding is None:
            self.sp = False
        pad = self.tp if self.sp else 1
        self._lane_C = shd.pad_tokens_to_tp(self.C, pad)
        self._lane_D = shd.pad_tokens_to_tp(self.D, pad)

    def activation_bytes_per_iteration(self) -> int:
        """Per-chip residual-stream footprint of one packed hybrid step:
        the two ``[T, d_model]`` norm+residual boundary activations per
        layer that sequence parallelism shards.  ``T`` is the compiled
        lane width ``C + D`` (padded to ``tp`` under SP) divided by ``tp``
        when SP is on — the measured counterpart of
        :func:`repro.sim.cost_model.sp_activation_bytes`."""
        t = self._lane_C + self._lane_D
        if self.sp:
            t //= self.tp
        itemsize = np.dtype(self.dtype).itemsize
        return 2 * self.cfg.n_layers * t * self.cfg.d_model * itemsize

    @property
    def paged(self) -> bool:
        return self.block_manager is not None

    # ----------------------------------------------------------- requests
    @obs.spanned("engine.add_request")
    def add_request(self, req_id: int, memory=None) -> int:
        """Assign a cache slot; seed cross-attention KV if the architecture
        consumes frontend embeddings (VLM image tiles / audio frames)."""
        if not self._free:
            raise RuntimeError("no free slots")
        slot = self._free.pop(0)
        self._slot_of[req_id] = slot
        # wipe any stale state left by a previous occupant of this slot
        # (ring-buffer positions, SSM/LRU recurrent state); full-attention
        # KV rows self-heal under the causal mask but are wiped too.
        self._wipe_slot(slot)
        if memory is not None:
            self._seed_memory(memory, slot)
        elif self.model.needs_memory:
            raise ValueError(f"{self.cfg.name} requires frontend embeddings")
        return slot

    def _wipe_slot(self, slot: int):
        self.cache = self._reset_slot(self.cache, jnp.int32(slot))

    def _seed_memory(self, memory, slot: int):
        if self.cfg.family == "encdec":
            memory = self.model.encode(self.params, memory[None])[0]
        self.cache = self._seed_cross(self.params, self.cache, memory, slot)

    @obs.spanned("engine.release")
    def release(self, req_id: int):
        slot = self._slot_of.pop(req_id)
        self._free.append(slot)
        if self.block_manager is not None:
            self.block_manager.free(req_id)   # idempotent vs scheduler free

    def slot(self, req_id: int) -> int:
        return self._slot_of[req_id]

    # ---------------------------------------------------------- KV handoff
    def extract_request(self, req_id: int) -> KVHandoff:
        """Extract ``req_id``'s cache state for relocation to another
        engine (phase-disaggregated serving, ``repro.serving.disagg``):
        every slot-indexed leaf's row plus — when paged — the request's
        pool-block contents gathered through its block table.  The
        reserved scratch block is never part of a table, so it is never
        transferred.  The payload is pulled to the host (``device_get``):
        that IS the replica-to-replica transfer, charged by the cost
        model's :func:`repro.sim.cost_model.kv_transfer_time` term.

        The request stays resident; callers release it afterwards."""
        slot = self._slot_of[req_id]
        table = (self.block_manager.table(req_id) if self.paged else [])
        state = jax.device_get(_extract_state(self.cache, slot, table))
        return KVHandoff(
            state=state, n_blocks=len(table),
            block_size=self.block_manager.block_size if self.paged else 0)

    def _prepare_install(self, req_id: int, handoff: KVHandoff
                         ) -> List[int]:
        """Shared install preconditions (single- and pipeline-engine):
        validate the payload against this engine's cache layout and
        allocate the FRESH destination block table; returns the table
        (empty for dense)."""
        if (handoff.n_blocks > 0) != self.paged:
            raise ValueError(
                "KV handoff requires matching cache layouts "
                f"(payload {'paged' if handoff.n_blocks else 'dense'}, "
                f"engine {'paged' if self.paged else 'dense'})")
        if not self.paged:
            return []
        bm = self.block_manager
        if handoff.block_size != bm.block_size:
            raise ValueError(
                f"KV handoff block_size mismatch: payload "
                f"{handoff.block_size}, engine {bm.block_size}")
        table = bm.ensure(req_id, handoff.n_blocks * bm.block_size)
        if len(table) != handoff.n_blocks:       # pre-existing allocation
            raise ValueError(
                f"req {req_id} already holds {len(table)} blocks on "
                f"the receiving engine; install needs a fresh slot")
        return table

    def install_request(self, req_id: int, handoff: KVHandoff):
        """Install an extracted payload into ``req_id``'s (already
        assigned) slot: rows land in the slot, pool blocks land in a
        FRESH block-table allocation from this engine's own pool — block
        ids are remapped, only contents move.  A pure relocation: greedy
        token outputs afterwards are bit-identical to never having left
        the source engine."""
        table = self._prepare_install(req_id, handoff)
        slot = self._slot_of[req_id]
        self.cache = _install_state(self.cache, handoff.state, slot, table)
        if self.tp_mesh is not None:
            # re-pin the policy shardings: the eager scatter above may
            # leave leaves with propagated (not canonical) placements
            from repro import sharding as shd
            self.cache = shd.shard_cache(self.cfg, self.cache, self.tp_mesh)

    # ------------------------------------------------------------- KV swap
    def _host_pool_for(self, cache):
        """A host (numpy) arena mirroring ``cache``'s pool leaves with the
        block axis resized to the manager's host-slot count; non-pool
        leaves are zero-size stand-ins so the tree walks line up with
        :func:`_gather_pool` results."""
        n = self.block_manager.n_host_slots

        def mk(path, leaf):
            lead, is_pool = _leaf_kind(path)
            if not is_pool:
                return np.zeros((0,), leaf.dtype)
            shape = leaf.shape[:lead] + (n,) + leaf.shape[lead + 1:]
            return np.zeros(shape, leaf.dtype)

        return jax.tree_util.tree_map_with_path(mk, cache)

    @staticmethod
    def _arena_store(arena, rows, slots):
        """Write the first ``len(slots)`` gathered rows into the arena's
        host slots (rows beyond that are scratch-padding)."""
        idx = np.asarray(slots, np.int64)

        def wr(path, a, r):
            lead, is_pool = _leaf_kind(path)
            if is_pool:
                sl = (slice(None),) * lead
                a[sl + (idx,)] = r[sl + (slice(0, len(idx)),)]
            return a

        jax.tree_util.tree_map_with_path(wr, arena, rows)

    @staticmethod
    def _arena_fetch(arena, slots, n_pad):
        """Read arena rows for ``slots``, zero-padded along the block axis
        to ``n_pad`` (the padded scatter writes the zeros into the
        reserved scratch block)."""
        idx = np.asarray(slots, np.int64)

        def rd(path, a):
            lead, is_pool = _leaf_kind(path)
            if not is_pool:
                return a
            sl = (slice(None),) * lead
            rows = a[sl + (idx,)]
            if n_pad > len(idx):
                pad = list(rows.shape)
                pad[lead] = n_pad - len(idx)
                rows = np.concatenate(
                    [rows, np.zeros(pad, a.dtype)], axis=lead)
            return rows

        return jax.tree_util.tree_map_with_path(rd, arena)

    def _swap_out_one(self, cache, arena, pairs):
        """Gather ``(device_block, host_slot)`` pairs' block contents off
        one cache tree and store them in its arena."""
        src, _ = _pad_pairs(pairs)
        rows = jax.device_get(self._gather_pool(cache, src))
        self._arena_store(arena, rows, [s for _, s in pairs])

    def _swap_in_one(self, cache, arena, pairs):
        """Stream ``(host_slot, device_block)`` pairs' contents from the
        arena back into one cache tree; returns the updated tree."""
        _, dst = _pad_pairs([(0, b) for _, b in pairs])
        rows = self._arena_fetch(arena, [s for s, _ in pairs], len(dst))
        return self._scatter_pool(cache, rows, dst)

    @obs.spanned("engine.swap_out")
    def swap_out_blocks(self, pairs: Sequence[tuple]):
        """Device->host move for :meth:`BlockManager.swap_out` pairs: the
        named device blocks' KV contents land in the host arena rows.
        Must run before any of those blocks is reallocated — the serving
        loops call this synchronously inside the preemption hook."""
        if not pairs:
            return
        if self._host_pool is None:
            self._host_pool = self._host_pool_for(self.cache)
        self._swap_out_one(self.cache, self._host_pool, pairs)

    @obs.spanned("engine.swap_in")
    def swap_in_blocks(self, pairs: Sequence[tuple]):
        """Host->device move for :meth:`BlockManager.swap_in` pairs,
        before the resumed request's next chunk: restores the exact KV
        bytes swapped out, so greedy outputs are bit-identical to never
        having been preempted."""
        if not pairs:
            return
        if self._host_pool is None:
            self._host_pool = self._host_pool_for(self.cache)
        self.cache = self._swap_in_one(self.cache, self._host_pool, pairs)

    # --------------------------------------------------------------- step
    def _step_impl(self, params, pk: PackedBatch, cache, key):
        chunk_logits, decode_logits, cache, _ = \
            self.model.forward_packed(params, pk, cache)
        with jax.named_scope("sample"):
            kc, kd = jax.random.split(key)
            chunk_tok = (sample(chunk_logits[0], kc, self.sampling)
                         if chunk_logits is not None else None)
            # sample only the REAL decode rows: SP pads the lanes to a
            # multiple of tp, and the PRNG's noise depends on the array
            # shape, so sampling the padded [lane_D, V] block would change
            # every stochastic decode stream vs the unpadded engine (a
            # static slice; no-op when the lanes are unpadded)
            dec_tok = (sample(decode_logits[:self.D], kd, self.sampling)
                       if decode_logits is not None else None)
        return chunk_tok, dec_tok, chunk_logits, cache

    @obs.spanned("engine.execute")
    def execute(self, plan: IterationPlan) -> Dict[int, int]:
        """Run one iteration; returns {req_id: newly sampled token} for the
        requests that produced a token this iteration.

        The compiled step is single-chunk (static shape ``(C, D)``); a
        multi-chunk plan is executed as consecutive packed sub-steps — the
        first carries all piggybacked decodes, the rest are chunk-only —
        so schedulers can fill a token budget larger than C without
        changing the engine contract.
        """
        if len(plan.decodes) > self.D:
            raise ValueError(f"plan has {len(plan.decodes)} decodes > D={self.D}")
        for c in plan.chunks:
            if len(c.tokens) > self.C:
                raise ValueError("chunk longer than engine chunk size")

        out: Dict[int, int] = {}
        chunks: List[Optional[ChunkWork]] = list(plan.chunks) or [None]
        for i, chunk in enumerate(chunks):
            out.update(self._execute_packed(
                chunk, plan.decodes if i == 0 else []))
        return out

    def warmup(self):
        """Compile both packed-step shapes — the hybrid ``(C, D)`` step (on
        a scratch chunk row) and the decode-only ``(0, D)`` step — WITHOUT
        consuming PRNG or iteration state, so a warmed engine replays a
        cold one exactly even under stochastic sampling."""
        key, n = self._key, self.iterations
        self._execute_packed(None, [], pad_chunk=True)
        self._execute_packed(None, [])
        self._key, self.iterations = key, n

    @obs.spanned("engine.pack")
    def _pack(self, chunk: Optional[ChunkWork],
              decodes: Sequence[DecodeWork],
              pad_chunk: bool = False) -> PackedBatch:
        """Host-side batch assembly shared by the single-device and
        pipeline engines: static-shape token/slot arrays plus (when paged)
        the per-request block tables, allocating what this iteration's
        writes need.

        A chunk-less iteration packs a ZERO-width chunk lane (the
        decode-only shape) unless ``pad_chunk`` forces the C-wide scratch
        lane (warmup's hybrid-shape compile).  Lane widths are the
        SP-padded ``_lane_C``/``_lane_D`` (equal to ``C``/``D`` when SP is
        off) so the packed token axis always splits evenly over ``tp``."""
        C_w = self._lane_C if (chunk is not None or pad_chunk) else 0
        ct = np.zeros((C_w,), np.int32)
        if chunk:
            ct[:len(chunk.tokens)] = chunk.tokens
            c_slot = self._slot_of[chunk.req_id]
            c_start = chunk.start
            c_len = len(chunk.tokens)
        else:
            c_slot, c_start, c_len = self.scratch, 0, 0

        dt = np.zeros((self._lane_D,), np.int32)
        ds = np.full((self._lane_D,), self.scratch, np.int32)
        dc = np.zeros((self._lane_D,), np.int32)
        for i, w in enumerate(decodes):
            dt[i] = w.token
            ds[i] = self._slot_of[w.req_id]
            dc[i] = w.ctx

        # block tables: allocate whatever this iteration's writes need
        # (idempotent when a block-aware scheduler already reserved);
        # padded entries point at the scratch block, so the scratch chunk
        # and unused decode lanes write into ONE reserved block instead of
        # a whole max_len scratch row
        M = self.blocks_per_seq
        cb = np.zeros((M,), np.int32)
        db = np.zeros((self._lane_D, M), np.int32)
        if self.paged:
            bm = self.block_manager
            # copy-on-write: any write landing in a block this request
            # does not exclusively own (prefix-shared) forks it first;
            # tables are read AFTER prepare_write so they list the forks
            pairs = []
            if chunk:
                bm.ensure(chunk.req_id, chunk.start + len(chunk.tokens))
                pairs += bm.prepare_write(
                    chunk.req_id, chunk.start,
                    chunk.start + len(chunk.tokens))
                cb = bm.padded_table(chunk.req_id, M)
            for i, w in enumerate(decodes):
                bm.ensure(w.req_id, w.ctx + 1)
                pairs += bm.prepare_write(w.req_id, w.ctx, w.ctx + 1)
                db[i] = bm.padded_table(w.req_id, M)
            if pairs:
                self._apply_cow(pairs)

        return PackedBatch(
            chunk_tokens=jnp.asarray(ct), chunk_slot=jnp.int32(c_slot),
            chunk_start=jnp.int32(c_start), chunk_len=jnp.int32(c_len),
            decode_tokens=jnp.asarray(dt), decode_slots=jnp.asarray(ds),
            decode_ctx=jnp.asarray(dc), chunk_blocks=jnp.asarray(cb),
            decode_blocks=jnp.asarray(db))

    @obs.spanned("engine.cow")
    def _apply_cow(self, pairs: Sequence[tuple]):
        """Run the copy-on-write block copies on device, before the packed
        step whose writes they protect."""
        src, dst = _pad_pairs(pairs)
        self.cache = self._cow_blocks(self.cache, src, dst)

    @staticmethod
    @obs.spanned("engine.collect")
    def _collect(chunk: Optional[ChunkWork], decodes: Sequence[DecodeWork],
                 chunk_tok, dec_tok) -> Dict[int, int]:
        out: Dict[int, int] = {}
        if chunk and chunk.is_last and chunk_tok is not None:
            out[chunk.req_id] = int(chunk_tok)
        if dec_tok is not None:
            dec_tok = np.asarray(dec_tok)
            for i, w in enumerate(decodes):
                out[w.req_id] = int(dec_tok[i])
        return out

    def _execute_packed(self, chunk: Optional[ChunkWork],
                        decodes: Sequence[DecodeWork],
                        pad_chunk: bool = False) -> Dict[int, int]:
        pk = self._pack(chunk, decodes, pad_chunk)
        self._key, sub = jax.random.split(self._key)
        if self.paged:
            # trace-time hint: a tp>1 mesh makes the pallas backend wrap
            # its kernel calls in shard_map over the kv-head axis (reset
            # per call so engines never see another engine's stale mesh)
            from repro.models import blocks as bk
            bk.set_paged_attn_mesh(self.tp_mesh)
        # trace-time SP hint (None when SP is off — always reset so one
        # engine never traces under another engine's stale sharding)
        from repro.models import stack as _stack
        _stack.set_packed_sp_sharding(self._sp_sharding)
        with obs.span("engine.launch"):
            chunk_tok, dec_tok, self.chunk_logits, self.cache = self._step(
                self.params, pk, self.cache, sub)
        self.iterations += 1
        return self._collect(chunk, decodes, chunk_tok, dec_tok)
