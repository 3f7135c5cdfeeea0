"""Pipeline-parallel stage partitioning of the grouped layer stack.

The model (``repro.models.stack``) stores its layers as a scanned
``groups`` tensor (leading axis = repeating group) plus explicit ``tail``
layers, so a PP partition is a pure *slicing* problem: stage ``s`` owns a
contiguous run of groups, stage 0 additionally owns the embedding, and the
last stage owns the tail layers, the final norm and the unembedding.
Because the partition only slices the scan — it never re-orders or re-fuses
a layer — composing the stage forwards is bit-identical to the monolithic
forward (``stack.forward_packed_stage``; pinned by
tests/test_stage_partition.py).

Placement goes through :func:`repro.launch.mesh.make_pipeline_mesh` when
enough devices exist: stage ``s`` lives on the mesh's ``s``-th device row
(:func:`stage_devices`).  On CPU CI the stage devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; fewer devices
than stages is an error, never a silent doubling-up of stages on one
device.  TP *within* a stage composes with
this partition: ``PipelineEngine(tp=...)`` places each stage's param and
cache slices over its stage row's ``model`` axis
(:func:`repro.sharding.stage_tp_meshes` + the shared policy leaf rules),
so every per-stage jitted step SPMD-partitions over ``tp`` chips while
the stage slicing stays a pure host-side tree operation.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.models import stack


def stage_bounds(n_groups: int, pp: int) -> List[Tuple[int, int]]:
    """Balanced contiguous split of ``n_groups`` scan groups into ``pp``
    stages: every stage gets >= 1 group (earlier stages take the
    remainder), so layer compute is as uniform per stage as the group
    granularity allows (the paper's §5.3 equal-split assumption)."""
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if pp > max(n_groups, 1):
        raise ValueError(
            f"pp={pp} exceeds the {n_groups} scan group(s) of this stack; "
            f"stage granularity is one group (= one repeating block "
            f"pattern, see repro.models.stack.group_split)")
    base, extra = divmod(n_groups, pp)
    bounds, g = [], 0
    for s in range(pp):
        n = base + (1 if s < extra else 0)
        bounds.append((g, g + n))
        g += n
    return bounds


def _slice_groups(tree: Dict, g0: int, g1: int) -> Dict:
    return jax.tree.map(lambda leaf: leaf[g0:g1], tree)


def stage_params(cfg: ModelConfig, params, pp: int) -> List[Dict]:
    """Split a full parameter tree into ``pp`` per-stage trees.

    Stage 0 carries ``embed`` (token embedding); the last stage carries
    ``tail`` + ``final_norm`` + the unembedding (which is ``embed`` again
    for tied-embedding models — both boundary stages then hold a copy)."""
    _, n_groups, _ = stack.group_split(cfg)
    out = []
    for s, (g0, g1) in enumerate(stage_bounds(n_groups, pp)):
        sp: Dict = {"groups": _slice_groups(params["groups"], g0, g1)}
        if s == 0:
            sp["embed"] = params["embed"]
        if s == pp - 1:
            sp["tail"] = params["tail"]
            sp["final_norm"] = params["final_norm"]
            if cfg.tie_embeddings:
                sp["embed"] = params["embed"]
            elif "unembed" in params:
                sp["unembed"] = params["unembed"]
        out.append(sp)
    return out


def stage_cache(cfg: ModelConfig, cache, pp: int) -> List[Dict]:
    """Split a full ``stack.init_cache`` tree into per-stage caches (the
    last stage also owns the tail layers' cache).  Works for dense and
    paged layouts alike — paged pool leaves are per-layer and slice with
    their group."""
    _, n_groups, _ = stack.group_split(cfg)
    out = []
    for s, (g0, g1) in enumerate(stage_bounds(n_groups, pp)):
        sc: Dict = {"groups": _slice_groups(cache["groups"], g0, g1)}
        if s == pp - 1:
            sc["tail"] = cache["tail"]
        out.append(sc)
    return out


def stage_devices(pp: int, devices: Optional[Sequence] = None) -> List:
    """One device per stage: row ``s`` of the
    :func:`repro.launch.mesh.make_pipeline_mesh` stage axis (which raises
    when there are fewer devices than stages)."""
    from repro.launch.mesh import make_pipeline_mesh
    mesh = make_pipeline_mesh(pp, 1, devices=devices)
    return [mesh.devices[s, 0] for s in range(pp)]


def place_stages(stage_trees: Sequence, devices: Sequence) -> List:
    """Commit each stage's tree to its stage device."""
    return [jax.device_put(tree, dev)
            for tree, dev in zip(stage_trees, devices)]
