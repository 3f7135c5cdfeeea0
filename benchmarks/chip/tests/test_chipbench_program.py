"""The program's spans and named scopes in a trace: how they are read, the
readings that need them, and where the program places them."""
from __future__ import annotations

from types import SimpleNamespace

import chipbench_tiny as T
import numpy as np
import pytest

from chipbench import measure
from chipbench import program as pm
from chipbench import spec
from chipbench import trace as tm

MS = 1_000_000        # ns
# the named scopes the program puts on its packed step
STEP_SCOPES = ("embed", "qkv", "kv_write", "kv_read", "attn", "o_proj",
               "ffn", "kv_carry", "unembed", "sample")
KV_SCOPES = ("kv_read", "kv_write", "kv_carry")
kv_move_share = spec.load_module(T.BENCH / "metrics" / "kv_move_share.py")


def _kv_share(prog, red):
    """The ``kv_move_share`` reader on what a traced run gives it."""
    return kv_move_share.read(measure.Reading(
        hf={}, terms=None, decode_lanes=1, chips=1, peaks={}, steps=[],
        traced=red, scope_s=pm.device_by_scope(prog, red.window)))


def test_scope_of_takes_the_innermost_scope_of_the_path():
    """An op's scope path ends in its innermost scope and keeps the outer
    ones before it."""
    body = "jit(_step_impl)/while/body/closed_call"
    sc = STEP_SCOPES
    assert pm.scope_path(f"{body}/attn/while/body/dot_general", sc) == \
        "attn"
    assert pm.scope_path(f"{body}/kv_carry/dynamic_update_slice", sc) == \
        "kv_carry"
    assert pm.scope_path("jit(f)/attn/x/kv_read/gather", sc) == \
        "attn/kv_read"
    assert pm.scope_path("ffn/reduce_sum", sc) == "ffn"
    assert pm.scope_path("jit(f)/qkvx/dot", sc) == ""
    assert pm.scope_path("%fusion.2 = bf16[4,8]", sc) == ""
    assert pm.scope_path("", sc) == ""
    # any scope the caller names, not a fixed list
    assert pm.scope_path("jit(f)/ffn/experts/dot", ("ffn", "experts")) == \
        "ffn/experts"


def test_named_scopes_of_a_traced_function():
    """Every ``jax.named_scope`` of a function, through a scan's body, a
    nested jit, a branch and a remat; no name JAX adds itself."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def inner(x):
        with jax.named_scope("ssm_scan"):
            return jnp.sin(x)

    def f(x):
        with jax.named_scope("outer"):
            def body(c, y):
                with jax.named_scope("experts"):
                    return c + inner(y), None
            c, _ = lax.scan(body, x[0], x)
            c = jax.checkpoint(jnp.cos)(c)
            with jax.named_scope("gate"):
                c = jnp.einsum("i,i->i", c, c)
                return lax.cond(c.sum() > 0, lambda z: z * 2,
                                lambda z: z, c)

    jaxpr = jax.jit(f).trace(jnp.ones((3, 4))).jaxpr.jaxpr
    assert pm.named_scopes(jaxpr) == {"outer", "experts", "ssm_scan",
                                      "gate"}


def _bench():
    """The benchmark's side: two steps on one chip (as in the trace
    tests).  Step 0's execute span [10, 30] ms holds device work [12, 20]
    and [18, 28]; step 1's [40, 60] holds [45, 55]; a wait over [60, 80]."""
    tr = tm.Trace()
    tr.device["/device:TPU:0"] = [(12 * MS, 20 * MS, "fusion.1"),
                                  (18 * MS, 28 * MS, "all-reduce.3"),
                                  (45 * MS, 55 * MS, "fusion.1")]
    tr.host = sorted([
        (0, 10 * MS, "bench.next_plan#0"),
        (10 * MS, 30 * MS, "bench.execute#0"),
        (30 * MS, 40 * MS, "bench.on_tokens#0"),
        (40 * MS, 60 * MS, "bench.execute#1"),
        (60 * MS, 80 * MS, "bench.wait"),
    ])
    return tr


def _program(tr, scopes=("kv_read", "", "ffn"), spans=True):
    """The program's side of the same trace: a scope on each operation and
    its spans inside the benchmark's.  Step 0 packs twice (two sub-steps,
    2 + 1 ms), step 1 once (2 ms)."""
    (ops,) = tr.device.values()
    prog = pm.Program(device={"/device:TPU:0": [
        (s, e, n, sc) for (s, e, n), sc in zip(ops, scopes)]})
    if spans:
        prog.host = sorted([
            (1 * MS, 9 * MS, "repro.sched.next_plan"),
            (11 * MS, 29 * MS, "repro.engine.execute"),
            (11 * MS, 13 * MS, "repro.engine.pack"),
            (20 * MS, 21 * MS, "repro.engine.pack"),
            (31 * MS, 39 * MS, "repro.sched.on_tokens"),
            (41 * MS, 59 * MS, "repro.engine.execute"),
            (41 * MS, 43 * MS, "repro.engine.pack"),
        ])
    return prog


def test_labels_sums_and_readings_on_a_synthetic_trace():
    tr = _bench()
    prog = _program(tr)
    red = tm.reduce(tr)
    # busy [12, 28] and [45, 55]; the gaps [55, 80], [28, 45], [0, 12]
    # have their middles in wait, sched.on_tokens, sched.next_plan
    gaps = pm.labelled_gaps(tr, prog, red)
    assert [g[0] for g in gaps] == ["wait", "sched.on_tokens",
                                    "sched.next_plan"]
    assert [g[1] for g in gaps] == pytest.approx([0.025, 0.017, 0.012])
    assert pm.idle_by_label(gaps, red.chips) == pytest.approx(
        {"wait": 0.025, "sched.on_tokens": 0.017, "sched.next_plan": 0.012})
    # leaves: [12, 20] kv_read, [18, 28] no scope, [45, 55] ffn
    assert pm.device_by_scope(prog, red.window) == pytest.approx(
        {"none": 0.010, "ffn": 0.010, "kv_read": 0.008})
    assert dict(pm.top_ops(prog, red.window)) == pytest.approx(
        {"all-reduce.3": 0.010, "ffn|fusion.1": 0.010,
         "kv_read|fusion.1": 0.008})
    # packs of 2 + 1 ms in step 0 and 2 ms in step 1
    assert pm.pack_ms_per_step(tr, prog, red.window) == pytest.approx(2.5)
    # program spans merge to [1, 9], [11, 29], [31, 39], [41, 59]: idle
    # 8 + (18 - 16) + 8 + (18 - 10) = 26 ms of the 80 ms window
    assert pm.idle_on_host_share(prog, red) == pytest.approx(32.5)
    # 8 ms under kv_read of 26 ms busy
    assert _kv_share(prog, red) == pytest.approx(100 * 8 / 26)
    assert pm.op_times(prog, red.window) == pytest.approx([
        ("all-reduce.3", "", 0.010), ("fusion.1", "ffn", 0.010),
        ("fusion.1", "kv_read", 0.008)])


def test_kv_move_share_counts_every_kv_scope():
    tr = _bench()
    prog = _program(tr, scopes=("kv_write", "kv_carry", "attn"))
    red = tm.reduce(tr)
    assert _kv_share(prog, red) == pytest.approx(100 * 18 / 26)


def test_a_scope_nested_under_a_kv_scope_still_counts_as_kv():
    """A scope the program adds inside ``kv_read`` (a kernel's own, or a
    finer span) keeps ``kv_read`` on its ops' paths: ``kv_move_share``
    reads the same share with the inner scope as without it."""
    import jax
    import jax.numpy as jnp

    def step(x, inner):
        with jax.named_scope("kv_read"):
            y = jnp.sin(x)
            if inner:
                with jax.named_scope("unfold_rows"):
                    y = y @ y
            else:
                y = y @ y
        with jax.named_scope("ffn"):
            return jnp.tanh(y) * 3.0

    shares, paths = [], []
    for inner in (False, True):
        traced = jax.jit(step, static_argnums=1).trace(
            jnp.ones((8, 8), jnp.float32), inner)
        names = pm.named_scopes(traced.jaxpr.jaxpr)
        sc = pm.hlo_scopes([traced.lower().compile().as_text()], names)
        paths.append(set(sc.values()))
        ops = [(i * 10 * MS, (i * 10 + 4) * MS, f"{lab} = x", p)
               for i, (lab, p) in enumerate(sorted(sc.items()))]
        prog = pm.Program(device={"/device:TPU:0": ops})
        red = SimpleNamespace(window=(0, 10 * MS * len(ops)),
                              busy_ns=4 * MS * len(ops),
                              busy_s=4e-3 * len(ops))
        n_kv = sum(1 for *_, p in ops if "kv_read" in p.split("/"))
        assert n_kv >= 1
        shares.append(_kv_share(prog, red))
        assert shares[-1] == pytest.approx(100 * n_kv / len(ops))
    assert "kv_read/unfold_rows" in paths[1]
    assert "kv_read/unfold_rows" not in paths[0]
    assert shares[1] == pytest.approx(shares[0])


def test_queue_wait_counts_the_unscheduled_with_their_wait_so_far():
    R = SimpleNamespace
    reqs = [R(arrival_time=9.0, first_scheduled=9.5),     # before w0
            R(arrival_time=10.0, first_scheduled=10.5),   # 0.5 s
            R(arrival_time=12.0, first_scheduled=None),   # 8 s so far
            R(arrival_time=15.0, first_scheduled=25.0),   # 5 s so far
            R(arrival_time=20.0, first_scheduled=20.1)]   # at w1: out
    assert pm.queue_wait_ms(reqs, 10.0, 20.0) == pytest.approx(
        1e3 * (0.5 + 8 + 5) / 3)
    assert pm.queue_wait_ms(reqs, 30.0, 40.0) is None
    # a program whose requests carry no stamp
    assert pm.queue_wait_ms([R(arrival_time=12.0)], 10.0, 20.0) is None


def _random_trace(seed, n_steps=60):
    """Driver-shaped spans (one call after another, idle waits between)
    and device work of random length inside the execute spans."""
    rng = np.random.default_rng(seed)
    tr, t = tm.Trace(), 0
    ops = []
    for k in range(n_steps):
        for kind in ("next_plan", "execute", "on_tokens"):
            d = int(rng.integers(1, 50)) * 1000
            tr.host.append((t, t + d, f"bench.{kind}#{k}"))
            if kind == "execute":
                a = t
                for _ in range(int(rng.integers(0, 4))):
                    a += int(rng.integers(0, 5)) * 1000
                    b = a + int(rng.integers(1, 20)) * 1000
                    ops.append((a, b, f"fusion.{rng.integers(0, 5)}"))
                    a = b
            t += d
        if rng.random() < 0.3:
            d = int(rng.integers(1, 200)) * 1000
            tr.host.append((t, t + d, "bench.wait"))
            t += d
        t += int(rng.integers(0, 3)) * 1000
    tr.device["/device:TPU:0"] = sorted(ops)
    tr.host.sort()
    return tr


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_without_program_spans_or_scopes_the_old_labels_stand(seed):
    """A trace with no ``repro.`` span and no scope (a program that has
    none) gets exactly ``trace.reduce``'s gap labels and top operations,
    and no reading of the program's metrics."""
    tr = _random_trace(seed)
    prog = pm.Program(device={p: [(s, e, n, "") for s, e, n in ops]
                              for p, ops in tr.device.items()})
    red = tm.reduce(tr)
    assert pm.labelled_gaps(tr, prog, red)[:10] == red.idle_gaps
    assert pm.top_ops(prog, red.window) == red.top_ops
    assert set(pm.device_by_scope(prog, red.window)) == {"none"}
    assert pm.pack_ms_per_step(tr, prog, red.window) is None
    assert pm.idle_on_host_share(prog, red) is None
    assert _kv_share(prog, red) is None


# ------------------------------------------------- spans of the program
def _inside(span, parents):
    return any(a <= span[0] and span[1] <= b for a, b, _ in parents)


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _profile(tmp_path, body):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = body()
    finally:
        jax.profiler.stop_trace()
    path = tm.find_xplane(str(tmp_path))
    return tm.load(path), pm.load(path), result


def test_program_spans_nest_in_the_drivers_calls(tmp_path):
    """A few steps of the tiny cell's server under the benchmark's driver:
    every scheduler and engine span appears, once per step or packed
    sub-step, inside the call that makes it."""
    import jax
    from chipbench import harness
    from chipbench.driver import Driver, clock
    cell = T.cell()
    srv, _, _ = harness.build(cell, 5, jax.devices())
    drv = Driver(srv.scheduler, srv.executor, annotate=True)
    drv.open_loop(harness.make_jobs(cell, 5, 1.0), clock())
    tr, prog, _ = _profile(tmp_path, lambda: drv.run(t_end=clock() + 1.5))
    spans = tm.step_spans(tr)
    assert len(drv.steps) >= 5 and any(len(s.chunks) > 1
                                       for s in drv.steps)
    names = {n for *_, n in prog.host}
    assert {"repro." + n for n in (
        "sched.next_plan", "sched.admit", "sched.on_tokens",
        "engine.execute", "engine.pack", "engine.launch", "engine.collect",
        "engine.add_request", "engine.release")} <= names
    for step in drv.steps:
        sp = spans[step.idx]

        def within(kind, name, sp=sp):
            a, b = sp[kind]
            return [s for s in _named(prog.host, "repro." + name)
                    if a <= s[0] and s[1] <= b]

        n_sub = max(len(step.chunks), 1)
        assert len(within("next_plan", "sched.next_plan")) == 1
        assert len(within("next_plan", "sched.admit")) == 1
        assert len(within("execute", "engine.execute")) == 1
        for part in ("pack", "launch", "collect"):
            assert len(within("execute", "engine." + part)) == n_sub, part
        assert len(within("on_tokens", "sched.on_tokens")) == 1
    admits = _named(prog.host, "repro.sched.admit")
    retires = (_named(prog.host, "repro.sched.on_tokens")
               + _named(prog.host, "repro.sched.preempt"))
    assert all(_inside(s, admits)
               for s in _named(prog.host, "repro.engine.add_request"))
    assert all(_inside(s, retires)
               for s in _named(prog.host, "repro.engine.release"))
    # every program span is inside one of the driver's calls
    assert all(_inside(s, tr.host) for s in prog.host)
    # the CPU has no device plane: no scoped operation to read
    assert prog.device == {}


def test_preemption_swap_and_copy_on_write_spans(tmp_path):
    """Pool pressure with a host tier swaps a request out and back in; an
    identical prompt with the prefix cache forks its tail block.  Each
    engine call sits in the scheduler call or the packing that makes it."""
    import itertools

    import jax
    import repro.scheduler.request as request_mod
    from chipbench import spec
    from repro.scheduler import Request
    from repro.serving import OnlineServer
    cfg = spec.program_config(T.config())
    from repro.models import build_model
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    kw = dict(chunk_size=8, n_slots=3, max_len=64, max_prompt_len=32,
              token_budget=16, paged=True, block_size=8)
    swap = OnlineServer(cfg, params, n_blocks=8, host_blocks=16,
                        preempt_mode="swap", **kw)
    share = OnlineServer(cfg, params, prefix_cache=True, **kw)
    swap.executor.warmup()
    share.executor.warmup()
    request_mod._ids = itertools.count()
    rng = np.random.default_rng(0)
    pressed = [Request(prompt=rng.integers(0, 256, 17).tolist(),
                       max_new_tokens=10) for _ in range(2)]
    prompt = rng.integers(0, 256, 16).tolist()
    same = [Request(prompt=list(prompt), max_new_tokens=4, arrival_time=t)
            for t in (0.0, 50.0)]

    def body():
        return (swap.run(pressed, warmup=False),
                share.run(same, warmup=False))

    _, prog, (res, _) = _profile(tmp_path, body)
    assert res.n_swap_outs > 0 and same[1].cached_tokens > 0
    h = prog.host
    for child, parent in (("engine.swap_out", "sched.preempt"),
                          ("engine.swap_in", "sched.admit"),
                          ("engine.cow", "engine.pack"),
                          ("engine.pack", "engine.execute")):
        kids = _named(h, "repro." + child)
        assert kids, child
        assert all(_inside(s, _named(h, "repro." + parent))
                   for s in kids), (child, parent)
    assert len(_named(h, "repro.sched.preempt")) == res.n_preemptions


_HLO = """HloModule jit_f, entry_computation_layout={(bf16[8,8]{1,0})->bf16[8,8]{1,0}}

FileNames
1 "f.py"

%fused_computation.1 (param_0.13: bf16[8,8]) -> f32[8,8] {
  %param_0.13 = bf16[8,8]{1,0} parameter(0)
  %sin.0 = f32[8,8]{1,0} sine(%param_0.13), metadata={op_name="jit(f)/qkv/sin" stack_frame_id=3}
  ROOT %convert.29 = f32[8,8]{1,0} convert(%sin.0)
}

ENTRY %main.5 (x: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8,8]{1,0} dot(%fusion.1, %fusion.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/while/body/kv_carry/dot_general"}
  ROOT %copy.3 = bf16[8,8]{1,0} copy(%dot.2)
}
"""


def test_scopes_from_compiled_hlo_text():
    """The compiled modules' text names each operation's scope: a fusion
    takes its fused computation's, a layout copy without metadata has
    none, and a label two modules scope apart is left out.  A device
    event's name (the op's HLO text, layouts and all) reads the same
    label."""
    sc = pm.hlo_scopes([_HLO], STEP_SCOPES)
    assert sc == {"%sin.0 = f32[8,8]": "qkv", "%fusion.1 = f32[8,8]": "qkv",
                  "%dot.2 = f32[8,8]": "kv_carry"}
    event = ("%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(bf16[8,8]{1,0} "
             "%x), kind=kLoop, calls=%fused_computation.1")
    assert sc[tm.op_label(event)] == "qkv"
    other = _HLO.replace("jit(f)/while/body/kv_carry", "jit(f)/ffn")
    assert pm.hlo_scopes([_HLO, other], STEP_SCOPES) == {
        "%sin.0 = f32[8,8]": "qkv", "%fusion.1 = f32[8,8]": "qkv"}
    unscoped = _HLO.replace(', metadata={op_name="jit(f)/while/body/'
                            'kv_carry/dot_general"}', "")
    assert "%dot.2 = f32[8,8]" not in pm.hlo_scopes([_HLO, unscoped],
                                                    STEP_SCOPES)
    # a scope outside the list is not read
    assert pm.hlo_scopes([_HLO], ("qkv",)) == {
        "%sin.0 = f32[8,8]": "qkv", "%fusion.1 = f32[8,8]": "qkv"}


def _tiny_engine():
    import jax
    import jax.numpy as jnp
    from repro.core.engine import Engine
    from repro.models import build_model
    cfg = spec.program_config(T.config())
    params = build_model(cfg).init_params(jax.random.PRNGKey(0),
                                          jnp.bfloat16)
    return Engine(cfg, params, n_slots=4, max_len=128, chunk_size=32,
                  decode_slots=3, dtype=jnp.bfloat16, paged=True,
                  block_size=16)


def test_compiled_step_scopes_cover_the_kv_movement():
    """On the tiny cell's compiled packed step, the scopes read from the
    HLO text name the pool gathers, scatters and the layer scan's carry."""
    sc = pm.step_scopes(_tiny_engine())
    assert set(KV_SCOPES) <= set(sc.values())
    assert {"qkv", "ffn", "attn", "unembed"} <= set(sc.values())


def test_step_scopes_read_as_the_fixed_list_did():
    """The scopes found on the packed step's jaxpr are the ten the
    program puts there, so every operation keeps the scope the fixed list
    gave it, and every reading by scope stays as it was."""
    eng = _tiny_engine()
    names, texts = set(), []
    for pad_chunk in (True, False):
        pk = eng._pack(None, [], pad_chunk=pad_chunk)
        traced = eng._step.trace(eng.params, pk, eng.cache, eng._key)
        names |= pm.named_scopes(traced.jaxpr.jaxpr)
        texts.append(traced.lower().compile().as_text())
    assert names == set(STEP_SCOPES)
    assert pm.step_scopes(eng) == pm.hlo_scopes(texts, STEP_SCOPES)
