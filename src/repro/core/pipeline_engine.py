"""Pipeline-parallel SARATHI execution engine (paper §5.3, operational).

The discrete-event simulator (``repro.sim.pipeline``) *predicts* that
uniform decode-maximal micro-batches shrink pipeline bubbles; this engine
*executes* that schedule.  The layer stack is partitioned into ``pp``
stages (``repro.launch.pipeline``), each stage owns its own slice of the
KV / state cache — dense rows or paged block pools alike — on its own
device, and every packed sub-step of an :class:`IterationPlan` flows
through the stages as one micro-batch.

Contract: drop-in for :class:`repro.core.engine.Engine` —
``add_request`` / ``release`` / ``execute(plan)`` / ``warmup`` behave
identically, and token outputs are BIT-identical to the single-device
engine on the same plan sequence (the stage partition slices the layer
scan without altering any per-layer computation, and the PRNG key is
split per packed sub-step in the same order).

Timing: stages run sequentially in-process (one micro-batch at a time,
stage by stage), which is *result*-equivalent to overlapped execution
because concurrent in-flight micro-batches touch disjoint requests (the
scheduler locks a request while its micro-batch is in flight), so their
cache writes commute.  Each stage call is measured on the wall clock —
including the activation transfer onto the stage's device, i.e. the real
P2P hop — and ``execute_timed`` hands the per-stage durations to the
serving loop, which reconstructs stage occupancy / bubbles on a virtual
pipeline clock (:class:`repro.serving.metrics.PipelineStats`) with exactly
the recurrence the simulator uses.  Measured bubbles are therefore
directly comparable to ``sim.pipeline`` predictions
(``benchmarks/pipeline.py``).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cache import BlockManager
from repro.configs.base import ModelConfig
from repro.core.engine import (ChunkWork, DecodeWork, Engine, IterationPlan,
                               KVHandoff, _extract_state, _install_state,
                               _pad_pairs)
from repro.core.sampling import SamplingParams, sample


class PipelineEngine(Engine):
    """``Engine`` over a ``pp``-stage partition of the layer stack, one
    (host or accelerator) device per stage — or, with ``tp > 1``, one
    ``tp``-chip tensor-parallel mesh row per stage (each stage's params
    and dense/paged cache slices shard over its row's ``model`` axis
    under the shared :mod:`repro.sharding` policy, and each per-stage
    jitted step SPMD-partitions accordingly).  Token outputs stay
    BIT-identical to the single-device engine at ``tp=1``; ``tp>1``
    matches to the documented tolerance tier (TP all-reduces reorder
    float accumulation — README §TPxPP)."""

    def __init__(self, cfg: ModelConfig, params, *, pp: int, n_slots: int,
                 max_len: int, chunk_size: int, decode_slots: int,
                 dtype=jnp.float32,
                 sampling: SamplingParams = SamplingParams(),
                 seed: int = 0, paged: bool = False,
                 block_size: int = 16, n_blocks: Optional[int] = None,
                 watermark: float = 0.0, host_blocks: int = 0,
                 block_manager: Optional[BlockManager] = None,
                 tp: int = 1, devices: Optional[Sequence] = None,
                 sp: bool = False):
        from repro.launch import pipeline as pl
        # tp is NOT forwarded: the monolithic cache built by Engine.__init__
        # is only the host-side source of the per-stage slices, which are
        # sharded per stage row below
        super().__init__(cfg, params, n_slots=n_slots, max_len=max_len,
                         chunk_size=chunk_size, decode_slots=decode_slots,
                         dtype=dtype, sampling=sampling, seed=seed,
                         paged=paged, block_size=block_size,
                         n_blocks=n_blocks, watermark=watermark,
                         host_blocks=host_blocks,
                         block_manager=block_manager)
        if self.model.needs_memory:
            raise NotImplementedError(
                f"{cfg.name}: cross-attention memory seeding is not "
                f"pipeline-partitioned yet (vlm/encdec)")
        self.pp = int(pp)
        self.tp = int(tp)
        stage_params = pl.stage_params(cfg, params, self.pp)
        stage_caches = pl.stage_cache(cfg, self.cache, self.pp)
        if self.tp > 1:
            from repro import sharding as shd
            shd.check_tp_supported(self.tp, self.paged, cfg)
            # stage s = row s of the (pp, tp) pipeline mesh; each row is a
            # (1, tp) ("data", "model") submesh the shared policy shards
            # the stage's param/cache slices over
            self.stage_meshes = shd.stage_tp_meshes(self.pp, self.tp,
                                                    devices)
            self.devices = [m.devices[0, 0] for m in self.stage_meshes]
            self._stage_put = [shd.replicated(m) for m in self.stage_meshes]
            self.stage_params = [shd.shard_params(cfg, t, m) for t, m
                                 in zip(stage_params, self.stage_meshes)]
            self.stage_caches = [shd.shard_cache(cfg, t, m) for t, m
                                 in zip(stage_caches, self.stage_meshes)]
        else:
            self.stage_meshes = None
            self.devices = pl.stage_devices(self.pp, devices)
            self._stage_put = list(self.devices)
            self.stage_params = pl.place_stages(stage_params, self.devices)
            self.stage_caches = pl.place_stages(stage_caches, self.devices)
        # SP re-resolves against the real per-stage tp (super().__init__
        # ran at tp=1 so its lane widths were the unpadded budgets); every
        # stage row has the same model-axis size, so one lane geometry and
        # one per-stage sharding list serve all stages
        self._init_sp(sp, self.stage_meshes[0] if self.stage_meshes else None)
        if self.sp:
            from repro import sharding as shd
            self._sp_shardings = [shd.sp_activation_sharding(m)
                                  for m in self.stage_meshes]
        else:
            self._sp_shardings = [None] * self.pp
        # the monolithic cache from Engine.__init__ was the source of the
        # per-stage slices (bit-identical initial state), now dropped
        self.cache = None
        self._stage_fns = []
        for s in range(self.pp):
            first, last = s == 0, s == self.pp - 1
            if last:
                impl = functools.partial(self._last_stage_impl, first=first)
            elif first:
                impl = self._first_stage_impl
            else:
                impl = self._mid_stage_impl
            # per-stage cache (arg 1) is donated: KV updates in place
            self._stage_fns.append(jax.jit(impl, donate_argnums=(1,)))
        self._x0 = jnp.zeros((0,), dtype)      # placeholder when pp == 1
        self._durs = [0.0] * self.pp           # per-stage wall time (s) of
        #                                        the last execute() call

    # ------------------------------------------------------- stage bodies
    def _first_stage_impl(self, params, cache, pk, x):
        # x is the zero-size placeholder; the first stage embeds pk's tokens
        x, cache, _ = self.model.forward_packed_stage(
            params, pk, cache, None, first=True, last=False)
        return x, cache

    def _mid_stage_impl(self, params, cache, pk, x):
        x, cache, _ = self.model.forward_packed_stage(
            params, pk, cache, x, first=False, last=False)
        return x, cache

    def _last_stage_impl(self, params, cache, pk, x, key, *, first):
        (chunk_logits, decode_logits), cache, _ = \
            self.model.forward_packed_stage(params, pk, cache, x,
                                            first=first, last=True)
        kc, kd = jax.random.split(key)
        chunk_tok = (sample(chunk_logits[0], kc, self.sampling)
                     if chunk_logits is not None else None)
        # real decode rows only — lane padding must not perturb the
        # sampling noise shape (see Engine._step_impl)
        dec_tok = (sample(decode_logits[:self.D], kd, self.sampling)
                   if decode_logits is not None else None)
        return chunk_tok, dec_tok, cache

    # --------------------------------------------------- engine overrides
    def _wipe_slot(self, slot: int):
        s32 = jnp.int32(slot)
        self.stage_caches = [self._reset_slot(c, s32)
                             for c in self.stage_caches]

    def _seed_memory(self, memory, slot: int):   # pragma: no cover - guarded
        raise NotImplementedError("PipelineEngine does not support "
                                  "frontend-memory architectures yet")

    @obs.spanned("engine.cow")
    def _apply_cow(self, pairs: Sequence[tuple]):
        # one engine-wide block id space; every stage's pool forks the
        # same (src, dst) pairs on its own cache slice
        src, dst = _pad_pairs(pairs)
        self.stage_caches = [self._cow_blocks(c, src, dst)
                             for c in self.stage_caches]

    @obs.spanned("engine.swap_out")
    def swap_out_blocks(self, pairs: Sequence[tuple]):
        # one engine-wide block id space, one host arena per stage: the
        # same (device_block, host_slot) moves replay on every stage's
        # pool slice (mirrors _apply_cow)
        if not pairs:
            return
        if self._host_pool is None:
            self._host_pool = [self._host_pool_for(c)
                               for c in self.stage_caches]
        for c, a in zip(self.stage_caches, self._host_pool):
            self._swap_out_one(c, a, pairs)

    @obs.spanned("engine.swap_in")
    def swap_in_blocks(self, pairs: Sequence[tuple]):
        if not pairs:
            return
        if self._host_pool is None:
            self._host_pool = [self._host_pool_for(c)
                               for c in self.stage_caches]
        self.stage_caches = [self._swap_in_one(c, a, pairs)
                             for c, a in zip(self.stage_caches,
                                             self._host_pool)]

    def extract_request(self, req_id: int) -> KVHandoff:
        """Per-stage extraction reassembled into the MONOLITHIC cache
        structure: the stage partition slices the scanned ``groups`` axis
        contiguously (``repro.launch.pipeline.stage_bounds``) and parks
        the tail on the last stage, so concatenating the per-stage
        payloads along the group axis in stage order IS the single-engine
        payload — handoff composes across replicas of unequal ``pp``."""
        slot = self._slot_of[req_id]
        table = (self.block_manager.table(req_id) if self.paged else [])
        parts = [jax.device_get(_extract_state(c, slot, table))
                 for c in self.stage_caches]
        state = {"groups": jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=0),
            *[p["groups"] for p in parts])}
        if "tail" in parts[-1]:
            state["tail"] = parts[-1]["tail"]
        return KVHandoff(
            state=state, n_blocks=len(table),
            block_size=self.block_manager.block_size if self.paged else 0)

    def install_request(self, req_id: int, handoff: KVHandoff):
        """Split the canonical payload back onto this engine's stage
        boundaries and install each slice into its stage cache (one
        engine-wide block table covers every stage's pool, exactly like
        the resident paged path)."""
        from repro.launch import pipeline as pl
        from repro.models import stack
        table = self._prepare_install(req_id, handoff)
        slot = self._slot_of[req_id]
        _, n_groups, _ = stack.group_split(self.cfg)
        for s, (g0, g1) in enumerate(pl.stage_bounds(n_groups, self.pp)):
            part = {"groups": jax.tree.map(lambda leaf: leaf[g0:g1],
                                           handoff.state["groups"])}
            if s == self.pp - 1 and "tail" in handoff.state:
                part["tail"] = handoff.state["tail"]
            self.stage_caches[s] = _install_state(
                self.stage_caches[s], part, slot, table)
        if self.stage_meshes is not None:
            from repro import sharding as shd
            self.stage_caches = [shd.shard_cache(self.cfg, c, m) for c, m
                                 in zip(self.stage_caches, self.stage_meshes)]

    def _execute_packed(self, chunk: Optional[ChunkWork],
                        decodes: Sequence[DecodeWork],
                        pad_chunk: bool = False) -> Dict[int, int]:
        pk = self._pack(chunk, decodes, pad_chunk)
        self._key, sub = jax.random.split(self._key)
        x = self._x0
        for s, fn in enumerate(self._stage_fns):
            last = s == self.pp - 1
            if self.paged:
                # per-stage trace-time mesh hint for the paged pallas
                # backend (each stage jits against its own (1, tp) row)
                from repro.models import blocks as bk
                bk.set_paged_attn_mesh(
                    self.stage_meshes[s] if self.stage_meshes else None)
            # per-stage SP hint (None when SP is off; each stage's jit
            # traces against its own mesh row's token sharding)
            from repro.models import stack as _stack
            _stack.set_packed_sp_sharding(self._sp_shardings[s])
            t0 = time.perf_counter()
            # the activation hop onto this stage's device(s) is part of the
            # stage's measured time (it IS the P2P transfer); with tp > 1
            # the target is the stage row's mesh, replicated
            x = jax.device_put(x, self._stage_put[s])
            if last:
                outs = fn(self.stage_params[s], self.stage_caches[s], pk,
                          x, sub)
                chunk_tok, dec_tok, self.stage_caches[s] = outs
                jax.block_until_ready(
                    [o for o in (chunk_tok, dec_tok) if o is not None])
            else:
                x, self.stage_caches[s] = fn(
                    self.stage_params[s], self.stage_caches[s], pk, x)
                jax.block_until_ready(x)
            self._durs[s] += time.perf_counter() - t0
        self.iterations += 1
        return self._collect(chunk, decodes, chunk_tok, dec_tok)

    def execute(self, plan: IterationPlan) -> Dict[int, int]:
        self._durs = [0.0] * self.pp
        return super().execute(plan)

    def execute_timed(self, plan: IterationPlan) \
            -> Tuple[Dict[int, int], List[float]]:
        """Run one iteration; returns ``(tokens, stage_durations)`` where
        ``stage_durations[s]`` is the measured wall time stage ``s`` spent
        on this plan (summed over the plan's packed sub-steps) — the
        micro-batch service times the serving loop's virtual pipeline
        clock consumes."""
        out = self.execute(plan)
        return out, list(self._durs)
