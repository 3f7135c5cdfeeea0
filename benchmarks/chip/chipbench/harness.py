"""One run of one cell: set-up, warm traffic, the measured window, the
trace's reduction, and the check.

Set-up (``setup_s``, from process start until the window opens):

1. weights made on the device from the seed in one jitted call;
2. the program's ``OnlineServer`` (policy ``sarathi_serve``, paged pool)
   built on them with the cell's geometry, and ``Engine.warmup`` compiling
   its two step shapes;
3. one warm request of two chunks and a few tokens served to its end, so
   that admission, the multi-chunk prefill, decoding, release and slot
   reset have all run once;
4. the cell's traffic served for ``warmup_s`` seconds, so that the queue
   is in its steady state when the window opens.

No knob of the program is set: every cell runs its defaults.

A traced run profiles the whole window.  After it, while the engine is
alive, the step's two shapes are traced for their named scopes and
compiled for their HLO text (``program.step_scopes``), so the readers get
device time by scope and by operation beside the trace's busy time
(``measure.Reading``).
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
from typing import Callable, Optional

from chipbench import check, counts, measure, program, spec, traffic, weights
from chipbench import trace as trace_mod
from chipbench.driver import Driver, clock

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
WARM_ID = 10 ** 9


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts lowerings and compilations inside its ``with`` block."""

    def __init__(self):
        self.n = 0
        self.names: list = []

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            self.n += 1
            self.names.append(kw.get("fun_name", "?"))


def free(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def start_trace(log_dir: str) -> None:
    """Profile the device and the benchmark's host spans, not Python."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def make_jobs(cell: spec.Cell, seed: int, seconds: float):
    """The cell's open-loop requests: a warm-up segment, then the window."""
    return traffic.open_loop(cell.traffic, cell.cell["load"]["rate"],
                             [cell.cell["warmup_s"], seconds],
                             cell.config["vocab_size"], seed)[0]


def build(cell: spec.Cell, seed: int, devices,
          server_hook: Optional[Callable] = None):
    """Weights from the seed and the program's server on them, warmed up;
    returns (server, params, the weights' builder)."""
    import jax
    import jax.numpy as jnp
    from repro import env
    from repro.models import build_model
    from repro.serving import OnlineServer

    hf = cell.config
    geo = cell.cell["engine"]
    ref = cell.reference()
    cfg = ref.program_config(hf)
    t = clock()
    template = jax.eval_shape(
        lambda k: build_model(cfg).init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    make_weights = weights.Builder(ref.weight_specs(hf), hf["hidden_size"])
    params = weights.to_tree(make_weights(seed), template)
    say(f"weights from seed {seed} on {devices[0].device_kind}: "
        f"{clock() - t:.2f} s")
    t = clock()
    srv = OnlineServer(cfg, params, policy="sarathi_serve",
                       dtype=jnp.bfloat16, chunk_size=geo["chunk_size"],
                       n_slots=geo["n_slots"], max_len=geo["max_len"],
                       paged=True, block_size=geo.get("block_size", 16),
                       n_blocks=geo.get("n_blocks"), devices=devices)
    eng = srv.engine
    if server_hook is not None:
        server_hook(srv)
    srv.executor.warmup()
    warm = traffic.Job(WARM_ID, [1] * (2 * eng.C + 1), 4)
    drv = Driver(srv.scheduler, srv.executor)
    drv.open_loop([warm], clock())
    drv.run(t_end=clock() + 600, until_idle=True)
    jax.block_until_ready(eng.cache)
    say(f"engine: backend={env.get('REPRO_PAGED_ATTN_BACKEND')} C={eng.C} "
        f"D={eng.D} n_slots={eng.n_slots} max_len={eng.max_len} "
        f"pool_blocks={eng.block_manager.n_blocks} "
        f"block_size={eng.block_manager.block_size} "
        f"token_budget={srv.scheduler.token_budget}; "
        f"warmup {clock() - t:.2f} s")
    return srv, params, make_weights


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_proc: float, devices, peaks: Optional[dict] = None,
        server_hook: Optional[Callable] = None,
        on_trace: Optional[Callable] = None,
        control: bool = False) -> dict:
    """Serve the cell once and return the result line's object.

    ``server_hook(server)`` is called on the built server before warm-up
    (tests use it to break the timed path).  A traced run hands
    ``on_trace(trace, program_trace, reduced)`` what it read of the trace.
    With ``control`` the check compares the control (the reference one
    precision step lower) in the program's place, and has to come out not
    correct."""
    import jax
    dev = devices[0]
    if peaks is None:
        peaks = spec.peaks(dev.device_kind)
    srv, params, make_weights = build(cell, seed, devices, server_hook)
    eng = srv.engine
    hf = cell.config
    geo = cell.cell["engine"]
    ref = cell.reference()

    jobs = make_jobs(cell, seed, seconds)
    drv = Driver(srv.scheduler, srv.executor, annotate=trace)
    t_traffic = clock()
    w0 = t_traffic + cell.cell["warmup_s"]
    w1 = w0 + seconds
    drv.open_loop(jobs, t_traffic)
    drv.run(t_end=w0)
    setup_s = clock() - t_proc
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    with CompileCounter() as compiles:
        if trace:
            start_trace(trace_dir)
        drv.run(t_end=w1)
        if trace:
            jax.profiler.stop_trace()

    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices)
    in_win = measure.window_logs(drv.logs, w0, w1)
    late = [g.submitted - g.due for g in drv.logs.values()
            if g.submitted is not None and w0 <= g.due < w1]
    steps = [s for s in drv.steps if w0 <= s.plan[0] < w1]
    say(f"window {seconds} s: {len(steps)} steps, {len(in_win)} requests, "
        f"{sum(1 for g in in_win if g.finished and g.finished <= w1)} "
        f"finished, {drv.n_preemptions} preemptions, "
        f"{sum(1 for s in steps if s.chunks and s.decodes)} hybrid / "
        f"{sum(1 for s in steps if not s.chunks)} decode-only / "
        f"{sum(1 for s in steps if len(s.chunks) > 1)} multi-chunk steps")
    e2e = measure.end_to_end(drv.logs, w0, w1, setup_s)
    say("window statistics: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(e2e.items())))
    if late:
        say(f"generator lateness (submit - due): max {max(late) * 1e3:.3f} "
            f"ms, p99 {traffic.percentile(late, 99) * 1e3:.3f} ms")
    say(f"compiles inside the window: {compiles.n} {compiles.names[:5]}")
    say(f"memory_peak_bytes={mem}; setup_s={setup_s!r}")

    reading = measure.Reading(hf=hf, terms=counts.Terms(**ref.step_terms(hf)),
                              decode_lanes=eng.D, chips=cell.chips,
                              peaks=peaks, steps=steps)
    red = None
    if trace:
        t = clock()
        # the engine is still alive: its steps' scopes, compiled afresh
        scopes = program.step_scopes(eng)
        path = trace_mod.find_xplane(trace_dir)
        tr = trace_mod.load(path)
        prog = program.load(path, scopes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = trace_mod.reduce(tr)
        reading.traced = red
        reading.spans = trace_mod.step_spans(tr)
        if red is not None:
            reading.scope_s = program.device_by_scope(prog, red.window)
            reading.ops = program.op_times(prog, red.window)
            say(f"trace: {red.chips} chip(s), window {red.window_s!r} s, "
                f"busy {red.busy_s!r} s, {len(reading.traced_steps())} "
                f"traced steps, {len(set(scopes.values()))} scopes; "
                f"read in {clock() - t:.2f} s")
        if on_trace is not None:
            on_trace(tr, prog, red)

    # the check: state freed first, so the reference sets no peak
    limits = cell.cell["check"]
    sample = check.pick(drv.logs, seed, limits["requests"], w0, w1)
    free(eng.cache)
    free(params)
    del srv, eng, params
    gc.collect()
    t = clock()
    R = check.Reference(ref, hf, make_weights, seed, t_pad=geo["max_len"],
                        p_pad=cell.traffic["output"]["max"])
    gaps = [R.gaps(g) for g in sample]
    if control:
        served = max((float(x.max()) for x in gaps if len(x)), default=None)
        say(f"program's logit_gap_max beside the control: {served!r}")
        gaps = [R.control_gaps(g) for g in sample]
    R.free()
    n_tok = int(sum(len(x) for x in gaps))
    widest = max((float(x.max()) for x in gaps if len(x)), default=None)
    say(f"reference over {len(sample)} requests ({n_tok} served tokens, "
        f"prompts {[len(g.job.prompt) for g in sample]}): "
        f"{clock() - t:.2f} s")
    compared = {
        "logit_gap_max": (widest, limits["logit_gap_max"]),
        "window_compiles_max": (compiles.n, 0),
        "checked_tokens_min": (n_tok, limits["checked_tokens_min"]),
    }
    correct = (widest is not None and widest <= limits["logit_gap_max"]
               and compiles.n == 0
               and n_tok >= limits["checked_tokens_min"])

    if trace:
        values = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(reading)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in cell.end_to_end if m["name"] in e2e}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(in_win),
           "failed": measure.failures(drv.logs, w0, w1),
           "metrics": values, "device": device}
    if trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    for name, (v, lim) in compared.items():
        say(f"check {name}: {v!r} (limit {lim})")
    out["check"] = {name: {"value": v, "limit": lim}
                    for name, (v, lim) in compared.items()}
    return out
