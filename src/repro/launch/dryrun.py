"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract memory / FLOP / collective statistics.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape decode_32k [--multi-pod] [--json out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The compiled artifact proves the distribution config is coherent: sharding
mismatches, compile-time OOM, or unsupported collectives all fail here.
cost_analysis / memory_analysis / HLO collective bytes feed EXPERIMENTS.md
§Dry-run and §Roofline.
"""
import argparse
import os
import json
import pathlib
import re
import sys
import time
import traceback

import jax

from repro import env
from repro.configs import ASSIGNED, get_config
from repro.launch import shardings as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_dryrun

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every array shape in an HLO type string
    (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Per-collective-kind result bytes summed over the module (per-device
    traffic proxy: the bytes each device materialises from the collective)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        kind = m.group(2)
        # skip -start/-done duplicates (counted once at -start)
        if "-done" in line.split("=", 1)[1].split("(")[0]:
            continue
        out[kind] += _shape_bytes(m.group(1))
        counts[kind] += 1
    return out, counts


def run_one(arch: str, shape: str, multi_pod: bool, variant: str = "",
            verbose: bool = True) -> dict:
    cfg = get_config(arch, variant=variant)
    mesh = make_production_mesh(multi_pod=multi_pod)
    report = {
        "arch": arch, "variant": variant, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.devices.size, "status": "ok",
        # REPRO_SCAN_UNROLL=1 makes cost_analysis count every layer (the
        # roofline pass); the rolled pass is the deployable artifact whose
        # memory_analysis matters.
        "unrolled": env.get("REPRO_SCAN_UNROLL"),
    }
    ok, why = sh.shape_supported(cfg, shape)
    if not ok:
        report["status"] = "skipped"
        report["reason"] = why
        if verbose:
            print(f"[skip] {arch} x {shape}: {why}")
        return report
    t0 = time.time()
    step, args, meta = build_dryrun(cfg, shape, mesh)
    report["optimizer"] = meta.get("optimizer")
    with mesh:
        jitted = jax.jit(step, donate_argnums=meta.get("donate", ()))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll, coll_counts = collective_bytes(hlo)

    report.update({
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", 0),
        },
        "collective_bytes": coll,
        "collective_counts": coll_counts,
    })
    if verbose:
        mb = 1 / (1 << 20)
        print(f"[ ok ] {arch} x {shape} @ {report['mesh']} "
              f"compile={t_compile:6.1f}s flops={report['flops']:.3e} "
              f"args={report['memory']['argument_bytes']*mb:9.0f}MiB "
              f"temp={report['memory']['temp_bytes']*mb:9.0f}MiB "
              f"coll={sum(coll.values())*mb:9.0f}MiB")
        print("  memory_analysis:", mem)
    return report


_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def ensure_host_devices(n: int = 512) -> None:
    """Merge ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS
    unless the caller already forces a device count.  Called from the CLI
    entrypoint (before the lazy XLA backend init reads the flag) instead
    of mutating ``os.environ`` unconditionally at import time — importing
    this module must not clobber a caller's flags depending on import
    order."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_COUNT_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{_DEVICE_COUNT_FLAG}={n} {flags}".strip()


def main(argv=None):
    ensure_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ASSIGNED) + [None])
    ap.add_argument("--variant", default="")
    ap.add_argument("--shape", default=None,
                    choices=sorted(sh.INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None, help="write report JSON here")
    args = ap.parse_args(argv)

    archs = sorted(ASSIGNED) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(sh.INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    reports, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    reports.append(run_one(arch, shape, mp, args.variant))
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mp, str(e)[:500]))
                    reports.append({
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "failed", "error": str(e)[:500]})
    if args.json:
        p = pathlib.Path(args.json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(reports, indent=2))
    n_ok = sum(r["status"] == "ok" for r in reports)
    n_skip = sum(r["status"] == "skipped" for r in reports)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped, "
          f"{len(failures)} failed ==")
    if failures:
        for f in failures:
            print("FAILED:", f)
        sys.exit(1)


if __name__ == "__main__":
    main()
