"""NamedSharding placement of live params / caches for the serving engines.

The launch stack (``repro.launch.steps``) consumes the policy as
ShapeDtypeStruct specs for dry-run lowering; the engines consume it here as
actual ``jax.device_put`` placements, so one leaf-rule module
(:mod:`repro.sharding.policy`) governs both.  With sharded inputs the
engines' jitted steps SPMD-partition automatically (GSPMD propagates from
the argument shardings); no shard_map or per-op annotation is needed.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding import policy


def make_tp_mesh(tp: int, devices: Optional[Sequence] = None) -> Mesh:
    """``(1, tp)`` mesh with the policy's ``("data", "model")`` axis names:
    the single-stage serving engine's TP domain.  The degenerate data axis
    keeps every policy spec valid on this mesh."""
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {len(devs)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            f"before the first jax call")
    arr = np.asarray(devs[:tp]).reshape(1, tp)
    return Mesh(arr, (policy.DATA, policy.MDL))


def stage_tp_meshes(pp: int, tp: int,
                    devices: Optional[Sequence] = None) -> List[Mesh]:
    """One ``(1, tp)`` submesh per pipeline stage — row ``s`` of
    :func:`repro.launch.mesh.make_pipeline_mesh`'s ``(pp, tp)`` grid — so
    each stage's jitted step SPMD-partitions over its own ``model`` axis
    while stages stay independent executables."""
    from repro.launch.mesh import make_pipeline_mesh
    grid = make_pipeline_mesh(pp, tp, devices=devices)
    return [Mesh(grid.devices[s].reshape(1, tp), (policy.DATA, policy.MDL))
            for s in range(pp)]


def shard_params(cfg: ModelConfig, params, mesh: Mesh):
    """Commit a (full or stage-sliced) parameter tree to ``mesh`` under the
    shared policy's PartitionSpecs."""
    specs = policy.param_pspecs(cfg, params, mesh=mesh)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params, specs)


def shard_cache(cfg: ModelConfig, cache, mesh: Mesh, *,
                rows_axes: Optional[tuple] = None):
    """Commit a (full or stage-sliced) cache tree — dense rows and paged
    ``pk``/``pv`` pools alike — to ``mesh``.  Engine slots are not batch-
    sharded (``rows_axes=None``): every device holds every slot's row, and
    the model axis splits KV heads / pool blocks / head_dim per policy."""
    specs = policy.cache_pspecs(cfg, cache, rows_axes=rows_axes, mesh=mesh)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        cache, specs)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated placement on ``mesh`` (activations crossing a
    pipeline-stage boundary, host-built packed batches)."""
    return NamedSharding(mesh, P())


def sp_activation_sharding(mesh: Optional[Mesh]) -> Optional[NamedSharding]:
    """NamedSharding for the sequence-parallel packed residual stream, or
    ``None`` when the mesh is absent / has no real model axis (tp=1) — the
    engines then skip the constraint entirely, keeping the unsharded trace
    byte-for-byte untouched.  Built as a NamedSharding (not a bare
    PartitionSpec) because the jitted packed steps do not run inside a
    ``with mesh:`` context."""
    if mesh is None:
        return None
    spec = policy.sp_activation_pspec(mesh=mesh)
    if spec is None:
        return None
    return NamedSharding(mesh, spec)


def pad_tokens_to_tp(n: int, tp: int) -> int:
    """Packed token count padded up to a multiple of ``tp`` so the SP
    token axis splits evenly.  Pad rows are masked downstream: chunk lanes
    beyond ``chunk_len`` already contribute nothing (attention/sampling
    mask on the packed chunk), and pad decode lanes target the scratch
    slot exactly like unused decode lanes do."""
    if tp <= 1:
        return int(n)
    return -(-int(n) // tp) * tp


def check_tp_supported(tp: int, paged: bool,
                       cfg: Optional[ModelConfig] = None) -> None:
    """TP support check for the paged attention backends.  GSPMD cannot
    partition a ``pallas_call``, so the block-table kernels run under
    shard_map over the kv-head axis instead (``repro.models.blocks``) —
    which needs whole kv heads per shard, i.e. ``n_kv_heads % tp == 0``.
    Reject the indivisible case up front instead of failing opaquely at
    trace time; the XLA gather backend
    partitions under any divisibility (the policy falls back to block or
    head_dim sharding)."""
    if tp <= 1 or not paged:
        return
    from repro.models.blocks import _paged_attn_backend
    if _paged_attn_backend() != "pallas":
        return
    nk = cfg.n_kv_heads if cfg is not None else None
    if nk is None or nk % tp:
        raise NotImplementedError(
            f"tp={tp} with the paged pallas attention backend needs "
            f"n_kv_heads divisible by tp (got n_kv_heads={nk}): the "
            f"kernels shard_map over the kv-head axis and each shard "
            f"must hold whole kv heads; use "
            f"REPRO_PAGED_ATTN_BACKEND=xla for this config")
