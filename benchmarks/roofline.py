"""Roofline analysis (deliverable g): derive the three roofline terms per
(arch x shape) from the dry-run's compiled artifacts.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

Sources: compiled.cost_analysis() for FLOPs/bytes; HLO-text parse for
collective bytes (see repro.launch.dryrun.collective_bytes).  Two
corrections applied and recorded:

* XLA reports cost_analysis for the whole partitioned module divided across
  devices already (CPU SPMD) — we treat the reported numbers as per-device.
* lax.scan bodies are counted ONCE by cost_analysis; the dry-run therefore
  compiles analysis artifacts with REPRO_SCAN_UNROLL=1 where feasible, and
  otherwise we scale the scan-body dominated terms by the trip count
  (recorded in the 'correction' column).

MODEL_FLOPS = 6·N·D (training) / 2·N·D (inference fwd) with N = active
params; the ratio MODEL_FLOPS / HLO_FLOPs flags remat/redundancy waste.

The module also carries the PAGED-KERNEL bandwidth table (``python -m
benchmarks.roofline``): an analytical achieved-vs-peak HBM bandwidth
model for the paged attention kernel variants (split vs fused pool
layout x single vs multi-buffered DMA), emitted as the deterministic
``BENCH_roofline_kernels.json`` artifact and gated by
check_regression.py.  See :func:`kernel_variant_rows`.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional

from repro.configs import get_config
from repro.launch.shardings import INPUT_SHAPES
from repro.models.stack import group_split
from repro.sim.hardware import TPU_V5E

CHIPS = {"16x16": 256, "2x16x16": 512}


def model_flops(arch: str, shape: str, variant: str = "") -> float:
    """Analytical useful FLOPs for the workload (per step, all chips)."""
    cfg = get_config(arch, variant=variant)
    info = INPUT_SHAPES[shape]
    n_active = cfg.active_param_count()
    if info["kind"] == "train":
        tokens = info["seq_len"] * info["global_batch"]
        return 6.0 * n_active * tokens
    if info["kind"] == "prefill":
        tokens = info["seq_len"] * info["global_batch"]
        return 2.0 * n_active * tokens
    tokens = info["global_batch"]                 # decode: one token/seq
    return 2.0 * n_active * tokens


def scan_correction(arch: str, shape: str, variant: str = "") -> float:
    """Trip-count factor when the artifact was compiled with the layer scan
    rolled (cost_analysis counts the body once)."""
    cfg = get_config(arch, variant=variant)
    _, n_groups, _ = group_split(cfg)
    return float(max(n_groups, 1))


def roofline_row(rep: Dict, *, corrected: bool = True) -> Optional[Dict]:
    if rep.get("status") != "ok":
        return None
    hw = TPU_V5E
    chips = CHIPS[rep["mesh"]]
    corr = 1.0
    if corrected and not rep.get("unrolled", False):
        corr = scan_correction(rep["arch"], rep["shape"],
                               rep.get("variant", ""))
    flops = rep["flops"] * corr
    byts = rep["bytes_accessed"] * corr
    coll = sum(rep["collective_bytes"].values())   # outside-scan collectives
    t_compute = flops / hw.peak_flops
    t_memory = byts / hw.hbm_bw
    t_coll = coll / hw.link_bw
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    mf = model_flops(rep["arch"], rep["shape"], rep.get("variant", ""))
    mf_per_chip = mf / chips
    return {
        "arch": rep["arch"], "shape": rep["shape"], "mesh": rep["mesh"],
        "compute_s": t_compute, "memory_s": t_memory,
        "collective_s": t_coll, "dominant": dominant,
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_ratio": mf_per_chip / flops if flops else 0.0,
        "scan_correction": corr,
    }


def load_and_summarise(json_path: str) -> List[Dict]:
    reps = json.loads(pathlib.Path(json_path).read_text())
    rows = []
    for r in reps:
        row = roofline_row(r)
        if row:
            rows.append(row)
    return rows


def rows_to_csv(rows: List[Dict]) -> List[str]:
    out = []
    for r in rows:
        out.append(
            f"roofline/{r['arch']}/{r['shape']}@{r['mesh']},"
            f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.1f},"
            f"dom={r['dominant']};c={r['compute_s'] * 1e3:.3f}ms;"
            f"m={r['memory_s'] * 1e3:.3f}ms;x={r['collective_s'] * 1e3:.3f}ms;"
            f"useful={r['useful_flops_ratio']:.2f}")
    return out


# --------------------------------------------------------------------------
# Paged-attention kernel variants: achieved vs model HBM bandwidth
# --------------------------------------------------------------------------
# Fixed decode/prefill geometry for the table — one representative serving
# point (per layer, per step).  Constants, not knobs: the artifact must be
# byte-stable so check_regression can gate it.
KERNEL_GEOM = dict(
    batch=8,          # decode sequences / packed prefill rows in flight
    chunk=256,        # prefill chunk tokens (SARATHI chunked prefill)
    n_q_heads=16, n_kv_heads=4, head_dim=128,
    block_size=16, pages_per_seq=64,          # ctx = 1024 tokens
    dtype_bytes=2,                            # bf16 pools
)
# Latency-equivalent cost of issuing ONE block-table DMA descriptor,
# expressed in HBM bytes (descriptor setup + first-beat latency at ~1
# GHz x ~1 TB/s).  The split pool pays this PER K AND PER V fetch; the
# fused pool's per-head [2, bs, hd] K/V page pair pays it once.
DMA_OVERHEAD_BYTES = 1024


def _kernel_variant_row(kernel: str, layout: str, buffering: str) -> Dict:
    g = KERNEL_GEOM
    hw = TPU_V5E
    n_rows = g["batch"] * g["n_kv_heads"] * g["pages_per_seq"]
    # useful traffic: every variant reads the SAME K+V payload (+ q in,
    # o out) — layouts change descriptor count, not payload
    kv_payload = (n_rows * g["block_size"] * 2 * g["head_dim"]
                  * g["dtype_bytes"])
    q_tokens = g["batch"] if kernel == "decode" else g["chunk"]
    qo_payload = 2 * q_tokens * g["n_q_heads"] * g["head_dim"] \
        * g["dtype_bytes"]
    payload = kv_payload + qo_payload
    # descriptor count: split issues separate K and V copies per
    # (seq/row, kv head, page); fused fetches the head's K/V pair once
    n_dma = n_rows * (2 if layout == "split" else 1)
    modeled_bytes = payload + n_dma * DMA_OVERHEAD_BYTES
    # time: DMA stream vs flash compute; multi-buffering overlaps them
    # behind a one-page pipeline fill, single-buffering serialises
    flops = 4.0 * q_tokens * g["n_q_heads"] * g["pages_per_seq"] \
        * g["block_size"] * g["head_dim"]
    if kernel == "prefill":
        flops *= 0.5                          # causal: ~half the scores
    t_dma = modeled_bytes / hw.hbm_bw
    t_compute = flops / hw.peak_flops
    if buffering == "multi":
        # overlap, paid for by one pipeline-fill page fetch up front
        page_bytes = (g["block_size"] * 2 * g["head_dim"]
                      * g["dtype_bytes"]
                      + (2 if layout == "split" else 1)
                      * DMA_OVERHEAD_BYTES)
        t_total = max(t_dma, t_compute) + page_bytes / hw.hbm_bw
    else:
        t_total = t_dma + t_compute
    achieved_bw = payload / t_total
    return {
        "kernel": kernel, "layout": layout, "buffering": buffering,
        "payload_bytes": payload, "modeled_bytes": modeled_bytes,
        "n_dma": n_dma,
        "model_bw_gbs": hw.hbm_bw / 1e9,
        "throughput": achieved_bw / 1e9,      # achieved GB/s (gated)
        "bw_fraction": achieved_bw / hw.hbm_bw,
    }


def kernel_variant_rows() -> List[Dict]:
    """The (kernel x layout x buffering) bandwidth table.  Two invariants
    are asserted here because the artifact gates on them implicitly:
    the fused layout strictly reduces modeled HBM bytes per step (half
    the DMA descriptors for the same payload), and multi-buffering never
    slows a variant down."""
    rows = [_kernel_variant_row(k, lo, bu)
            for k in ("decode", "prefill")
            for lo in ("split", "fused")
            for bu in ("single", "multi")]
    by = {(r["kernel"], r["layout"], r["buffering"]): r for r in rows}
    for k in ("decode", "prefill"):
        for bu in ("single", "multi"):
            assert (by[(k, "fused", bu)]["modeled_bytes"]
                    < by[(k, "split", bu)]["modeled_bytes"]), \
                f"fused must reduce modeled bytes ({k}/{bu})"
        for lo in ("split", "fused"):
            assert (by[(k, lo, "multi")]["throughput"]
                    >= by[(k, lo, "single")]["throughput"]), \
                f"multi-buffering must not regress bandwidth ({k}/{lo})"
    return rows


# --------------------------------------------------------------------------
# Parametric tile-time model (autotuner backend, tools/autotune_tiles.py)
# --------------------------------------------------------------------------
# Per-grid-step fixed cost of the pallas kernel (grid bookkeeping, scalar
# prefetch reads, loop-carried flash state handling), expressed in HBM
# bytes like DMA_OVERHEAD_BYTES.  REPRO_PAGED_KV_PAGES pages fetched per
# grid step amortise this over kv_pages; the per-page DMA descriptor
# overhead does NOT amortise (pool blocks are non-contiguous, every page
# needs its own copy descriptor).
GRID_STEP_OVERHEAD_BYTES = 512
# VMEM working-set budget per core (pallas guide: ~16 MB/core); the
# autotuner rejects tile choices whose double-buffered KV pages + q/o
# tiles exceed this.
VMEM_BYTES = 16 * 1024 * 1024


def tile_variant_time(kernel: str, *, kv_pages: int, q_block: int,
                      n_buffers: int) -> Optional[Dict]:
    """Modelled execution time of the FUSED-pool paged attention kernel at
    one (``kv_pages``, ``q_block``, ``n_buffers``) tile point — the three
    ``REPRO_PAGED_*`` env knobs of ``repro.kernels.ops``.

    Extends :func:`_kernel_variant_row`'s bandwidth math (same payload,
    same per-page descriptor overhead) with the knob effects:

    * ``kv_pages`` — pages fetched per grid step: amortises the
      per-grid-step fixed cost (``GRID_STEP_OVERHEAD_BYTES``) but NOT the
      per-page DMA descriptors (pool blocks are non-contiguous), and
      multiplies the VMEM KV working set;
    * ``q_block`` — prefill q-tile rows: the KV stream is re-read once
      per q tile (``ceil(chunk / q_block)`` times), so bigger tiles cut
      KV traffic at the price of a bigger VMEM q/o tile (decode has one
      q row per sequence; the knob is clamped to no effect there);
    * ``n_buffers`` — DMA buffers: 1 serialises fetch and compute,
      >= 2 overlaps them behind an ``(n_buffers - 1)``-page pipeline
      fill; every extra buffer adds a KV page to the VMEM working set.

    Returns ``None`` when the point exceeds the ``VMEM_BYTES`` budget
    (an invalid configuration, not a slow one)."""
    if kernel not in ("decode", "prefill"):
        raise ValueError(kernel)
    if kv_pages < 1 or q_block < 1 or n_buffers < 1:
        raise ValueError("tile knobs must be >= 1")
    g = KERNEL_GEOM
    hw = TPU_V5E
    page_rows = g["block_size"] * 2 * g["head_dim"] * g["dtype_bytes"]
    n_rows = g["batch"] * g["n_kv_heads"] * g["pages_per_seq"]
    q_tokens = g["batch"] if kernel == "decode" else g["chunk"]
    qb = q_tokens if kernel == "decode" else min(q_block, g["chunk"])
    n_q_tiles = -(-q_tokens // qb)
    # VMEM working set: buffered KV pages + one q tile + one o tile (+ the
    # flash running state, negligible next to the tiles)
    q_tile_bytes = qb * g["n_q_heads"] * g["head_dim"] * g["dtype_bytes"]
    vmem = n_buffers * kv_pages * page_rows + 2 * q_tile_bytes
    if vmem > VMEM_BYTES:
        return None
    # traffic: each q tile re-streams the full KV (+ per-page descriptor),
    # and each grid step (kv_pages pages) pays the fixed step cost once
    kv_payload = n_rows * page_rows
    qo_payload = 2 * q_tokens * g["n_q_heads"] * g["head_dim"] \
        * g["dtype_bytes"]
    n_steps = -(-n_rows // kv_pages)
    modeled_bytes = (n_q_tiles * (kv_payload + n_rows * DMA_OVERHEAD_BYTES
                                  + n_steps * GRID_STEP_OVERHEAD_BYTES)
                     + qo_payload)
    flops = 4.0 * q_tokens * g["n_q_heads"] * g["pages_per_seq"] \
        * g["block_size"] * g["head_dim"]
    if kernel == "prefill":
        flops *= 0.5                          # causal: ~half the scores
    t_dma = modeled_bytes / hw.hbm_bw
    t_compute = flops / hw.peak_flops
    if n_buffers >= 2:
        fill_bytes = (n_buffers - 1) * kv_pages \
            * (page_rows + DMA_OVERHEAD_BYTES)
        t_total = max(t_dma, t_compute) + fill_bytes / hw.hbm_bw
    else:
        t_total = t_dma + t_compute
    return {
        "kernel": kernel, "kv_pages": kv_pages, "q_block": qb,
        "n_buffers": n_buffers, "modeled_bytes": modeled_bytes,
        "vmem_bytes": vmem, "time_s": t_total,
    }


# --------------------------------------------------------------------------
# Sequence-parallel cost table: tp x sp from the analytical model
# --------------------------------------------------------------------------
# Fixed serving point for the SP table (one decode-maximal hybrid
# iteration of the paper's GPT-3 config).  Constants, not knobs: the
# artifact must be byte-stable so check_regression can gate it.
SP_GEOM = dict(arch="paper-gpt3-175b", chunk=256, n_decodes=8, ctx=1024)


def sp_variant_rows() -> List[Dict]:
    """The ``tp x sp`` cost table behind README §Tensor parallelism's SP
    claim, from :func:`repro.sim.cost_model.iteration_time`: sequence
    parallelism shards the non-matmul "others" term (norms, residual
    adds) and the inter-block activation bytes by ``tp`` while moving the
    same collective payload as the all-reduce it replaces.  Asserted here
    because the artifact gates on it: at ``tp >= 2`` the SP rows must
    show strictly lower ``others_s`` and ``activation_bytes``; at
    ``tp = 1`` SP must be an exact no-op."""
    from repro.sim.cost_model import (BatchSpec, DecodeSeg, PrefillSeg,
                                      iteration_time, sp_activation_bytes)
    g = SP_GEOM
    cfg = get_config(g["arch"])
    hw = TPU_V5E
    spec = BatchSpec(prefills=(PrefillSeg(g["chunk"], g["ctx"]),),
                     decodes=(DecodeSeg(g["n_decodes"], g["ctx"]),),
                     fused=True)
    n_tokens = g["chunk"] + g["n_decodes"]
    rows = []
    for tp in (1, 2, 4):
        for sp in (0, 1):
            bd = iteration_time(cfg, hw, spec, n_chips=tp, sp=bool(sp))
            rows.append({
                "tp": tp, "sp": sp,
                "others_s": bd.others, "collective_s": bd.collective,
                "activation_bytes": sp_activation_bytes(
                    cfg, n_tokens, n_chips=tp, sp=bool(sp)),
                "total_s": bd.total,
                "throughput": n_tokens / bd.total,    # tokens/s (gated)
            })
    by = {(r["tp"], r["sp"]): r for r in rows}
    for tp in (2, 4):
        assert by[(tp, 1)]["others_s"] < by[(tp, 0)]["others_s"], \
            f"SP must shard the others term (tp={tp})"
        assert (by[(tp, 1)]["activation_bytes"]
                < by[(tp, 0)]["activation_bytes"]), \
            f"SP must shrink activation bytes (tp={tp})"
    assert by[(1, 1)] == {**by[(1, 0)], "sp": 1}, \
        "SP at tp=1 must be an exact no-op"
    return rows


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="emit the paged-kernel bandwidth table "
                    "(BENCH_roofline_kernels.json) and the tp x sp "
                    "sequence-parallel cost table (BENCH_roofline_sp.json)")
    ap.add_argument("--out", default="BENCH_roofline_kernels.json",
                    help="kernel table path ('' disables)")
    ap.add_argument("--sp-out", default="BENCH_roofline_sp.json",
                    help="sequence-parallel table path ('' disables)")
    args = ap.parse_args(argv)
    rows = kernel_variant_rows()
    for r in rows:
        print(f"{r['kernel']:8s} {r['layout']:6s} {r['buffering']:7s} "
              f"bytes={r['modeled_bytes']:>9d} dma={r['n_dma']:>5d} "
              f"achieved={r['throughput']:7.1f} GB/s "
              f"({r['bw_fraction']:.0%} of model bw)")
    if args.out:
        pathlib.Path(args.out).write_text(
            json.dumps({"bench": "roofline_kernels", "rows": rows},
                       indent=1))
        print(f"wrote {args.out} ({len(rows)} rows)")
    if args.sp_out:
        sp_rows = sp_variant_rows()
        for r in sp_rows:
            print(f"tp={r['tp']} sp={r['sp']} "
                  f"others={r['others_s'] * 1e3:8.3f}ms "
                  f"coll={r['collective_s'] * 1e3:8.3f}ms "
                  f"act={r['activation_bytes'] / 1e6:8.1f}MB "
                  f"tput={r['throughput']:9.1f} tok/s")
        pathlib.Path(args.sp_out).write_text(
            json.dumps({"bench": "roofline_sp", "rows": sp_rows}, indent=1))
        print(f"wrote {args.sp_out} ({len(sp_rows)} rows)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
