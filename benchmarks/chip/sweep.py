#!/usr/bin/env python3
"""Find a cell's knee: serve its open-loop traffic at several fixed rates
in one process and report what each sustains.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --rates 2,3,4,5 --seconds 20

For each rate, on a server built afresh (from the process's compiled
programs), after the cell's ``warmup_s`` of that rate's traffic, it
prints one JSON line: requests completed per second, output tokens per
second (and those of requests due before the window opened) beside those
that the requests due in the window ask for, TTFT p50/p90, TBT
p50/p90/p99, and the backlog (requests waiting or running) when the
window opens and every 30 s after.  A rate the system sustains
completes requests and tokens at the rate offered and keeps its backlog
near its steady state; above the knee the backlog grows through the
window.  Windows of some minutes hold some hundreds of requests, so one
burst of the schedule does not decide a rate.
The benchmark's cells run at fixed rates found this way once; the runs of
``run.py`` never search for one.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from chipbench import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep.py: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import numpy as np
    from chipbench import harness, measure, traffic
    from chipbench.driver import Driver, clock
    warm = cell.cell["warmup_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        cell.cell["load"]["rate"] = rate
        srv, params, _ = harness.build(cell, args.seed, devices[:cell.chips])
        jobs = harness.make_jobs(cell, args.seed, args.seconds)
        drv = Driver(srv.scheduler, srv.executor)
        t0 = clock()
        w0, w1 = t0 + warm, t0 + warm + args.seconds
        drv.open_loop(jobs, t0)
        backlogs = []
        for at in [*np.arange(w0, w1, 30.0), w1]:
            drv.run(t_end=at)
            backlogs.append(len(srv.scheduler.waiting)
                            + len(srv.scheduler.running))
        done = [g for g in drv.logs.values()
                if g.finished is not None and w0 <= g.finished <= w1]
        t = measure.ttfts(drv.logs, w0, w1)
        g = measure.tbts(drv.logs, w0, w1)
        due = measure.due_in(drv.logs, w0, w1)
        early = {k: g for k, g in drv.logs.items() if g.due < w0}
        print(json.dumps({
            "rate": rate, "done_per_s": len(done) / args.seconds,
            "out_tok_s": measure.out_tokens(drv.logs, w0, w1) / args.seconds,
            "of_warmup_out_tok_s": measure.out_tokens(early, w0, w1)
            / args.seconds,
            "due_out_tok_s": sum(x.job.max_new_tokens for x in due)
            / args.seconds,
            "ttft_p50_ms": traffic.percentile(t, 50) * 1e3,
            "ttft_p90_ms": traffic.percentile(t, 90) * 1e3,
            "tbt_p50_ms": traffic.percentile(g, 50) * 1e3,
            "tbt_p90_ms": traffic.percentile(g, 90) * 1e3,
            "tbt_p99_ms": traffic.percentile(g, 99) * 1e3,
            "backlog_every_30s": backlogs,
            "waiting": len(srv.scheduler.waiting),
            "preemptions": drv.n_preemptions,
            "steps": sum(1 for s in drv.steps if w0 <= s.plan[0] < w1)}),
            flush=True)
        harness.free(srv.engine.cache)
        harness.free(params)
        del srv, params, drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
