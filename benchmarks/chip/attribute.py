#!/usr/bin/env python3
"""One run of one cell, as ``run.py`` makes it, with what the program's own
spans and named scopes add to the reading.

    python3 benchmarks/chip/attribute.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The run is ``run.py``'s (``harness.run``), and its last line is the same
JSON object with one more key, ``program``.  With ``--trace 1`` the trace
is also read for the program's ``repro.*`` spans and the scope of every
device operation (``chipbench/program.py``), and standard error gets:

* ``idle by label``: the window's idle seconds summed per innermost span
  (``engine.pack``, ``sched.next_plan``, ``wait``, ...);
* ``device by scope``: device seconds summed per named scope;
* the top operations with their scopes and the longest idle gaps with
  their labels;
* ``pack_ms_per_step``, ``idle_on_host_share`` and, where the cell has
  the requests, ``queue_wait_ms`` (the result line's ``metrics`` carry
  the cell's per-layer metrics, ``kv_move_share`` among them).

Either way it prints the Python garbage collector's pauses in the window
and the longest engine calls, to tell a stall of the host from one of the
device.  It compiles every program afresh (no persistent cache), so its
``setup_s`` reads higher than ``run.py``'s.  ``BENCHMARK.json``'s command
is ``run.py``; this script is for reading where the time goes.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Recorder:
    """What the run's host does, on the host's clock: the garbage
    collector's pauses, every ``Engine.execute`` call, every request."""

    def __init__(self):
        self.gc = []            # (start, seconds, generation)
        self.execute = []       # (start, seconds)
        self.requests = []      # (submitted, request)
        self.engine = None
        self._gc_t = None

    def on_gc(self, phase, info):
        from chipbench.driver import clock
        if phase == "start":
            self._gc_t = clock()
        elif self._gc_t is not None:
            self.gc.append((self._gc_t, clock() - self._gc_t,
                            info["generation"]))
            self._gc_t = None

    def hook(self, srv):
        """``harness.run``'s server hook: keep the requests, time the
        engine's calls."""
        from chipbench.driver import clock
        submit, execute = srv.scheduler.submit, srv.engine.execute

        def kept(req):
            self.requests.append((clock(), req))
            submit(req)

        def timed(plan):
            t = clock()
            try:
                return execute(plan)
            finally:
                self.execute.append((t, clock() - t))

        self.engine = srv.engine
        srv.scheduler.submit = kept
        srv.engine.execute = timed


def host_report(rec: Recorder, w0: float, w1: float, say) -> dict:
    pauses = [(t, s, g) for t, s, g in rec.gc if w0 <= t < w1]
    calls = sorted(((s, t) for t, s in rec.execute if w0 <= t < w1),
                   reverse=True)
    say(f"gc pauses in the window: {len(pauses)}, "
        f"{sum(s for _, s, _ in pauses):.4f} s in all, longest "
        f"{max((s for _, s, _ in pauses), default=0.0):.4f} s; "
        f"generation 2: {sum(1 for *_, g in pauses if g == 2)}")
    for s, t in calls[:5]:
        inside = [p for p in pauses if t <= p[0] <= t + s]
        say(f"engine.execute {s * 1e3:.2f} ms at window+{t - w0:.3f} s; "
            f"gc inside: {sum(p[1] for p in inside) * 1e3:.2f} ms")
    return {"gc_pauses": len(pauses),
            "gc_s": sum(s for _, s, _ in pauses),
            "gc_max_s": max((s for _, s, _ in pauses), default=0.0),
            "execute_max_ms": calls[0][0] * 1e3 if calls else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from chipbench import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"attribute.py: {args.workload} needs {cell.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 2
    # compile afresh: the persistent cache's key leaves metadata out, so
    # a step cached from code without the named scopes (another commit's)
    # would run, and be read, without them
    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench import harness, program

    seen = {}

    def on_trace(tr, prog, red):
        seen.update(trace=tr, program=prog, reduced=red)

    rec = Recorder()
    gc.callbacks.append(rec.on_gc)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_proc=T_PROC, devices=devices[:cell.chips],
                          server_hook=rec.hook, on_trace=on_trace)
    finally:
        gc.callbacks.remove(rec.on_gc)

    # requests arrive at ``arrival_time`` on the traffic's clock, which
    # starts at the host-clock time submission less arrival reads least;
    # the window is [warmup_s, warmup_s + seconds) on it
    timed = [(t, r) for t, r in rec.requests if r.req_id != harness.WARM_ID]
    w0 = cell.cell["warmup_s"]
    rel = {"queue_wait_ms": program.queue_wait_ms(
        [r for _, r in timed], w0, w0 + args.seconds)}
    start = min(t - r.arrival_time for t, r in timed)
    extra = host_report(rec, start + w0, start + w0 + args.seconds,
                        harness.say)
    if seen.get("reduced") is not None:
        tr, prog, red = seen["trace"], seen["program"], seen["reduced"]
        gaps = program.labelled_gaps(tr, prog, red)
        idle = program.idle_by_label(gaps, red.chips)
        scopes = program.device_by_scope(prog, red.window)
        ops = program.top_ops(prog, red.window)
        rel.update(
            pack_ms_per_step=program.pack_ms_per_step(tr, prog, red.window),
            idle_on_host_share=program.idle_on_host_share(prog, red))
        harness.say(f"program spans: {len(prog.host)}")
        harness.say("idle by label (s): " + ", ".join(
            f"{k} {v!r}" for k, v in idle.items()))
        harness.say("device by scope (s): " + ", ".join(
            f"{k} {v!r}" for k, v in scopes.items()))
        for n, s in ops:
            harness.say(f"top op {s!r} s: {n}")
        for n, s in gaps[:10]:
            harness.say(f"idle gap {s!r} s: {n}")
        extra.update(idle_by_label=idle, device_by_scope=scopes,
                     device_ops=[list(x) for x in ops],
                     idle_gaps=[list(x) for x in gaps[:10]])
    for k, v in rel.items():
        harness.say(f"{k}: {v!r}")
    out["program"] = dict(rel, **extra)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
