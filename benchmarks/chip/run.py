#!/usr/bin/env python3
"""Chip benchmark of the SARATHI serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the checkout's root, on a machine whose JAX finds the TPU chips
the cell asks for (``BENCHMARK.json``).  Without them it exits 2 and
prints no result.  The run builds the weights from the seed, serves the
cell's traffic through the program's scheduler and engine on the host's
clock, measures ``--seconds`` seconds after a warm-up, checks the served
tokens against the configuration's plain reference, and prints one JSON
object as its last line: the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics, read from a profiler trace of the whole window,
with ``--trace 1``.  A traced run compiles every program afresh, without
the persistent compilation cache, so that the step's named scopes are
the ones its operations carry.  Progress and the numbers compared with their
limits go to standard error.

``--control 1`` puts the check's control (the reference one precision step
lower) in the program's place; such a run has to print ``correct`` false.
It is how the limit's upper reading is taken, never part of a measurement.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
# libtpu logs to /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if args.trace:
        # the persistent cache's key leaves metadata out: a step cached
        # from code without the named scopes would run, and be read,
        # without them
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # every program, however quick to compile, comes from the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_proc=T_PROC, devices=devices[:cell.chips],
                      control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
