"""Per-architecture PartitionSpec policy — ONE copy of the leaf rules,
shared by the launch-time dry-run stack (``repro.launch``) and the serving
engines (``repro.core.Engine(tp=...)`` / ``repro.core.PipelineEngine``).

Sharding policy (see DESIGN.md §5):

* Megatron TP over the ``model`` axis: attention head projections, FFN
  hidden dim, vocab (embed/unembed), SSD inner channels/heads, RG-LRU
  width/gate blocks — sharded only when divisible by the axis size,
  replicated otherwise (the fallback is recorded per-leaf and revisited in
  the §Perf hillclimb).
* MoE expert parallelism over the ``data`` axis when n_experts divides it
  (llama4 128e/16) + TP over ``model`` inside each expert; otherwise experts
  replicate and only d_ff shards (granite-moe's 40e).
* FSDP over ``data`` on d_model dims for dense archs whose TP-sharded
  weights exceed the per-chip budget (llama-3.2-vision-90b).
* The ``pod`` axis is pure data parallelism (batch only).

Axis sizes are derived from the mesh actually in use (``mesh=``); the
bare-int ``model_axis=``/``data_axis=`` escape hatch exists for spec-only
unit tests.  An axis that is absent from the mesh (or has size 1) never
shards — the emitted specs then reference only axis names the mesh has,
so the same rules serve the 16x16 production mesh, a ``(1, tp)`` serving
mesh, and a pipeline stage row alike.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

MDL = "model"
DATA = "data"

# the production mesh edge (repro.launch.mesh.make_production_mesh); used
# only when neither mesh= nor an explicit axis size is given
DEFAULT_AXIS = 16

# Leaf names for which the fall-through replicate rule is INTENTIONAL.
# The static analyzer (tools/analysis, pass sharding-rules) builds every
# arch's param + cache pytree and requires each leaf name to be matched by
# an explicit rule branch below or declared here — so a new cache/param
# leaf can never silently replicate under TP again (the `pkv` pool leaf
# did exactly that until PR 4 caught it by hand).
PARAM_REPLICATED_OK = frozenset({"final_norm", "ln1", "ln2", "lnc"})
CACHE_REPLICATED_OK = frozenset()


def mesh_axis(mesh, name: str) -> int:
    """Size of mesh axis ``name``; 0 when the mesh lacks it (a 0-sized
    axis never shards anything, see :func:`_divides`)."""
    if mesh is None:
        return 0
    return dict(mesh.shape).get(name, 0)


def batch_axis_size(mesh) -> int:
    """Total batch-parallel ways of a mesh: ``data x pod`` (absent axes
    count as 1) — what global batches and MoE dispatch shard over."""
    return max(mesh_axis(mesh, DATA), 1) * max(mesh_axis(mesh, "pod"), 1)


def _resolve_axes(mesh, model_axis: Optional[int],
                  data_axis: Optional[int]) -> Tuple[int, int]:
    """Axis sizes from the mesh when given, else explicit ints, else the
    production default."""
    if mesh is not None:
        if model_axis is not None or data_axis is not None:
            raise ValueError("pass either mesh= or explicit axis sizes, "
                             "not both")
        return mesh_axis(mesh, MDL), mesh_axis(mesh, DATA)
    return (DEFAULT_AXIS if model_axis is None else model_axis,
            DEFAULT_AXIS if data_axis is None else data_axis)


def _divides(n: int, axis: int) -> bool:
    """Shard a dim of size ``n`` over ``axis`` chips: only when the axis
    is real (size > 1) and splits the dim evenly."""
    return axis > 1 and n % axis == 0


def _dense_param_bytes(cfg: ModelConfig) -> int:
    """Non-expert parameter bytes (bf16)."""
    return cfg.active_param_count() * 2


def use_fsdp(cfg: ModelConfig, model_axis: int = DEFAULT_AXIS) -> bool:
    """FSDP over data when plain TP leaves > ~9 GB/chip of weights."""
    return _dense_param_bytes(cfg) / max(model_axis, 1) > 9e9


def _axis(ok: bool, name: str) -> Optional[str]:
    return name if ok else None


def param_pspecs(cfg: ModelConfig, shapes, *, mesh=None,
                 model_axis: Optional[int] = None,
                 data_axis: Optional[int] = None):
    """shapes: pytree of ShapeDtypeStruct from jax.eval_shape(init_params)
    (or the parameter arrays themselves — only ``.shape`` is read).
    Returns a matching pytree of PartitionSpec."""
    model_axis, data_axis = _resolve_axes(mesh, model_axis, data_axis)
    fsdp = use_fsdp(cfg, model_axis) and data_axis > 1
    ep_ok = cfg.n_experts > 0 and _divides(cfg.n_experts, data_axis)

    def div(n: int, axis: int = model_axis) -> bool:
        return _divides(n, axis)

    def leaf_rule(path, leaf) -> P:
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        name = None
        for k in reversed(names):
            if isinstance(k, str):
                name = k
                break
        shp = leaf.shape
        grouped = "groups" in names or "layers" in names
        base = (None,) if grouped else ()
        r = len(shp) - len(base)                 # rank without group axis

        def spec(*dims):
            return P(*(base + dims))

        # ---- embeddings -------------------------------------------------
        if name == "embed":
            return P(_axis(div(shp[0]), MDL),
                     _axis(fsdp and div(shp[1], data_axis), DATA))
        if name == "unembed":
            return P(_axis(fsdp and div(shp[0], data_axis), DATA),
                     _axis(div(shp[1]), MDL))
        # ---- MoE --------------------------------------------------------
        if name == "router":
            return spec(None, None)
        if name in ("w_gate", "w_up") and r == 3:          # [E, d, f]
            return spec(_axis(ep_ok, DATA), None, _axis(div(shp[-1]), MDL))
        if name == "w_down" and r == 3:                    # [E, f, d]
            return spec(_axis(ep_ok, DATA), _axis(div(shp[-2]), MDL), None)
        # ---- dense FFN ----------------------------------------------------
        if name in ("w_gate", "w_up", "w1"):               # [d, f]
            return spec(_axis(fsdp and div(shp[-2], data_axis), DATA),
                        _axis(div(shp[-1]), MDL))
        if name in ("w_down", "w2"):                       # [f, d]
            return spec(_axis(div(shp[-2]), MDL),
                        _axis(fsdp and div(shp[-1], data_axis), DATA))
        # a column-parallel matmul's bias splits with its columns; a
        # row-parallel one's is replicated, so it is added once, to the
        # reduced sum, and not once per shard
        if name in ("b1", "b_gate", "b_up"):
            return spec(_axis(div(shp[-1]), MDL))
        if name in ("b2", "b_down"):
            return spec(None)
        # ---- attention ----------------------------------------------------
        if name == "wq":
            return spec(_axis(fsdp and div(shp[-2], data_axis), DATA),
                        _axis(div(shp[-1]), MDL))
        if name in ("wk", "wv"):
            return spec(_axis(fsdp and div(shp[-2], data_axis), DATA),
                        _axis(div(shp[-1]), MDL))
        if name == "wo":
            return spec(_axis(div(shp[-2]), MDL),
                        _axis(fsdp and div(shp[-1], data_axis), DATA))
        if name in ("bq", "bk", "bv"):
            return spec(_axis(div(shp[-1]), MDL))
        if name == "bo":
            return spec(None)
        # ---- SSD ----------------------------------------------------------
        if name in ("w_z", "w_x"):                         # [d, di]
            return spec(None, _axis(div(shp[-1]), MDL))
        if name in ("w_B", "w_C"):                         # replicate (small)
            return spec(None, None)
        if name == "w_dt":
            return spec(None, _axis(div(shp[-1]), MDL))
        if name in ("conv_x_w",):
            return spec(None, _axis(div(shp[-1]), MDL))
        if name in ("conv_x_b", "norm_w"):
            return spec(_axis(div(shp[-1]), MDL))
        if name in ("conv_B_w", "conv_C_w", "conv_B_b", "conv_C_b"):
            return spec(*(None,) * r)
        if name in ("a_log", "dt_bias", "d_skip"):
            return spec(_axis(div(shp[-1]), MDL))
        if name == "w_out":                                # [di|w, d]
            return spec(_axis(div(shp[-2]), MDL), None)
        # ---- RG-LRU --------------------------------------------------------
        if name in ("w_in_rec", "w_in_gate"):
            return spec(None, _axis(div(shp[-1]), MDL))
        if name == "conv_w":
            return spec(None, _axis(div(shp[-1]), MDL))
        if name in ("conv_b", "lam"):
            return spec(_axis(div(shp[-1]), MDL))
        if name in ("w_a", "w_i"):                         # [nb, bw, bw]
            return spec(_axis(div(shp[-3]), MDL), None, None)
        if name in ("b_a", "b_i"):
            return spec(_axis(div(shp[-2]), MDL), None)
        # ---- norms / scalars ------------------------------------------------
        return spec(*(None,) * r)

    return jax.tree_util.tree_map_with_path(leaf_rule, shapes)


def sp_activation_pspec(mesh=None, *,
                        model_axis: Optional[int] = None) -> Optional[P]:
    """PartitionSpec for a sequence-parallel packed activation: the rank-2
    ``[tokens, d_model]`` residual stream token-shards over the ``model``
    axis through the norm + residual region between the TP matmul blocks
    (Megatron sequence parallelism on the serving engines' packed path).

    Returns ``None`` when the mesh has no real model axis — SP on a
    ``tp=1`` mesh must leave the trace byte-for-byte untouched, so the
    caller simply skips the constraint.  The token count must be padded
    to a multiple of the axis size first (see
    :func:`repro.sharding.placement.pad_tokens_to_tp`)."""
    if mesh is not None:
        if model_axis is not None:
            raise ValueError("pass either mesh= or model_axis=, not both")
        model_axis = mesh_axis(mesh, MDL)
    elif model_axis is None:
        model_axis = DEFAULT_AXIS
    if model_axis <= 1:
        return None
    return P(MDL, None)


def kv_shard_mode() -> str:
    """§Perf knob for GQA caches whose n_kv_heads doesn't divide the model
    axis (would otherwise REPLICATE the cache, 16x memory):

    * "seq" (default): shard the cache's sequence dim (dense rows) or
      block-pool dim (paged) — decode attention becomes context-parallel;
      the combine is O(B·heads·hd);
    * "hd": shard head_dim — 16x storage cut but XLA all-gathers the cache
      (or all-reduces scores) per layer;
    * "none": paper-faithful replicated baseline.

    Set REPRO_SHARD_KV=seq|hd|none (registry-validated: anything else
    raises instead of silently acting like "none"; the legacy
    REPRO_SHARD_KV_HD spelling still resolves, with a DeprecationWarning).
    """
    from repro import env
    return env.get("REPRO_SHARD_KV")


def cache_pspecs(cfg: ModelConfig, shapes, *,
                 rows_axes: Optional[Tuple[str, ...]], mesh=None,
                 model_axis: Optional[int] = None):
    """Cache leaves: row (slot) dim shards over the batch axes; KV head /
    state-head dims shard over model when divisible.  The fused paged
    block-pool leaf (``pkv``, ``[n_blocks, nk, 2, block_size, hd]``) has
    no row dim — it shards the kv-head axis over model when ``nk``
    divides (each head's (K, V) pair stays on one shard), falling back
    to the block dim (context-parallel analogue) or head_dim per
    :func:`kv_shard_mode`, so the pool never silently replicates under
    TP."""
    if mesh is not None:
        if model_axis is not None:
            raise ValueError("pass either mesh= or model_axis=, not both")
        model_axis = mesh_axis(mesh, MDL)
    elif model_axis is None:
        model_axis = DEFAULT_AXIS

    def div(n):
        return _divides(n, model_axis)

    kv_mode = kv_shard_mode()
    rspec = rows_axes if rows_axes else None

    def leaf_rule(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        name = None
        for k in reversed(names):
            if isinstance(k, str):
                name = k
                break
        shp = leaf.shape
        grouped = "groups" in names
        base = (None,) if grouped else ()
        r = len(shp) - len(base)

        def spec(*dims):
            return P(*(base + dims))

        if name in ("k", "v", "ck", "cv"):  # [rows, S|W|F, nk, hd]
            if div(shp[-2]):
                return spec(rspec, None, MDL, None)
            if kv_mode == "seq" and div(shp[-3]):
                return spec(rspec, MDL, None, None)      # context parallel
            if kv_mode in ("seq", "hd") and div(shp[-1]):
                return spec(rspec, None, None, MDL)
            return spec(rspec, None, None, None)
        if name == "pkv":    # fused pool [N, nk, 2, bs, hd] (or r, 128)
            if div(shp[-4]):
                return spec(None, MDL, None, None, None)
            if kv_mode == "seq" and div(shp[-5]):
                return spec(MDL, None, None, None, None)  # block parallel
            if kv_mode in ("seq", "hd") and div(shp[-1]):
                return spec(None, None, None, None, MDL)
            return spec(None, None, None, None, None)
        if name == "pos":                   # [rows, W]
            return spec(rspec, None)
        if name == "state":                 # [rows, nh, P, N]
            return spec(rspec, _axis(div(shp[-3]), MDL), None, None)
        if name == "conv_x":                # [rows, cw-1, di]
            return spec(rspec, None, _axis(div(shp[-1]), MDL))
        if name in ("conv_B", "conv_C"):
            return spec(rspec, None, None)
        if name in ("h",):                  # [rows, w]
            return spec(rspec, _axis(div(shp[-1]), MDL))
        if name == "conv":                  # lru conv [rows, cw-1, w]
            return spec(rspec, None, _axis(div(shp[-1]), MDL))
        return spec(*(None,) * r)

    return jax.tree_util.tree_map_with_path(leaf_rule, shapes)


def with_sharding(mesh, shapes, pspecs):
    """Attach NamedShardings to a ShapeDtypeStruct tree (no allocation)."""
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, pspecs)
