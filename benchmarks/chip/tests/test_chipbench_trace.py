"""Trace reduction and the per-layer readers, on synthetic and recorded
traces."""
from __future__ import annotations

import chipbench_tiny as T  # noqa: F401  (puts the benchmark on sys.path)
import pytest

from chipbench import counts, measure, spec
from chipbench import trace as tm
from chipbench.driver import Step

MS = 1_000_000        # ns


def test_merge_overlap_gaps():
    m = tm.merge([(5, 7), (0, 2), (1, 3), (9, 9), (6, 10)])
    assert m == [(0, 3), (5, 10)]
    assert tm.overlap(m, 0, 10) == 8
    assert tm.overlap(m, 2, 6) == 2
    assert tm.overlap(m, 3, 5) == 0
    assert tm.overlap([], 0, 10) == 0
    assert tm.gaps(m, 0, 12) == [(3, 5), (10, 12)]
    assert tm.gaps(m, 4, 6) == [(4, 5)]


def _synthetic():
    """Two steps on one chip: step 0's execute span [10, 30] ms holds
    device work [12, 20] and [18, 28]; step 1's [40, 60] holds [45, 55].
    Host spans around them; a wait span over [60, 80]."""
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


def test_reduce_synthetic():
    tr = _synthetic()
    red = tm.reduce(tr)
    assert red.window == (0, 80 * MS)
    assert red.chips == 1
    assert red.busy_ns == 26 * MS                    # 16 + 10
    assert red.device_ns(10 * MS, 30 * MS) == 16 * MS
    assert red.collective_ns == 10 * MS
    assert red.top_ops[0] == ("fusion.1", pytest.approx(0.018))
    # longest gap [55, 80] ms (middle in the wait span), then [28, 45]
    # (middle 36.5 ms, in on_tokens), then [0, 12] (in next_plan)
    assert [g[0] for g in red.idle_gaps] == ["wait", "on_tokens",
                                             "next_plan"]
    assert [g[1] for g in red.idle_gaps] == pytest.approx(
        [0.025, 0.017, 0.012])
    spans = tm.step_spans(tr)
    assert spans[0]["execute"] == (10 * MS, 30 * MS)
    assert set(spans[0]) == {"next_plan", "execute", "on_tokens"}


def test_reduce_two_chips_and_empty():
    tr = _synthetic()
    tr.device["/device:TPU:1"] = [(10 * MS, 30 * MS, "fusion.2")]
    red = tm.reduce(tr)
    assert red.chips == 2
    assert red.busy_ns == (26 + 20) * MS / 2
    assert red.device_ns(10 * MS, 30 * MS) == (16 + 20) * MS / 2
    assert tm.reduce(tm.Trace()) is None


def _reading(tr, hf):
    red = tm.reduce(tr)
    steps = [Step(0, (0, 0.01), (0.01, 0.03), (0.03, 0.04),
                  chunks=[(0, 256, True)], decodes=[100, 200]),
             Step(1, (0.04, 0.04), (0.04, 0.06), (0.06, 0.06),
                  chunks=[], decodes=[300, 400, 500])]
    return measure.Reading(hf=hf, decode_lanes=4, chips=1,
                           peaks={"bf16_flops_per_s": 1e14,
                                  "hbm_bytes_per_s": 1e12},
                           steps=steps, traced=red,
                           spans=tm.step_spans(tr))


def _metric(name):
    return spec.load_module(T.BENCH / "metrics" / f"{name}.py").read


def test_readers_on_synthetic_trace():
    hf = T.config()
    r = _reading(_synthetic(), hf)
    assert [s.idx for s in r.traced_steps()] == [0, 1]
    assert _metric("device_idle_share")(r) == pytest.approx(
        100 * (1 - 26 / 80))
    assert _metric("decode_lanes_used")(r) == pytest.approx(
        100 * 5 / 8)
    # execute spans 20 ms each; device 16 and 10 ms inside them
    assert _metric("engine_host_ms_per_step")(r) == pytest.approx(7.0)
    # step 0 has both host spans (10 + 10 ms); step 1 has neither
    assert _metric("sched_ms_per_step")(r) == pytest.approx(20.0)
    flops = (counts.step_flops(hf, [(0, 256, True)], [100, 200])
             + counts.step_flops(hf, [], [300, 400, 500]))
    assert _metric("step_mfu")(r) == pytest.approx(
        100 * flops / (0.026 * 1e14))
    need = counts.decode_step_bytes(hf, [300, 400, 500])
    assert _metric("step_hbm_share.decode")(r) == pytest.approx(
        100 * need / (0.010 * 1e12))


def test_readers_find_nothing_without_a_trace():
    r = _reading(_synthetic(), T.config())
    r.traced, r.spans = None, {}
    for name in ("device_idle_share", "engine_host_ms_per_step",
                 "sched_ms_per_step", "step_mfu", "step_hbm_share.decode"):
        assert _metric(name)(r) is None, name
    r.steps = []
    assert _metric("decode_lanes_used")(r) is None


def test_recorded_cpu_trace_spans(tmp_path):
    """The host spans of a real profiler trace are found by name; the CPU
    has no device plane, so the reduction reports nothing."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench.execute#{i}"):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.wait"):
        pass
    jax.profiler.stop_trace()
    tr = tm.load(tm.find_xplane(str(tmp_path)))
    names = [n for _, _, n in tr.host]
    assert names[:3] == [f"bench.execute#{i}" for i in range(3)]
    assert "bench.wait" in names
    assert sorted(tm.step_spans(tr)) == [0, 1, 2]
    assert all(b >= a for a, b, _ in tr.host)
    assert tm.reduce(tr) is None


def test_innermost_ops_and_labels():
    ops = [(0, 100, "%while.1 = (s32[]) while(...)"),
           (0, 40, "%fusion.2 = bf16[4,8]{1,0:T(8,128)} fusion(...)"),
           (40, 100, "%copy.3 = bf16[2]{0} copy(...)"),
           (120, 130, "%dot.4 = f32[8,8]{1,0} dot(...)")]
    assert [n for *_, n in tm.leaves(sorted(ops))] == [ops[1][2], ops[2][2],
                                                       ops[3][2]]
    assert tm.op_label(ops[1][2]) == "%fusion.2 = bf16[4,8]"
    assert tm.op_label("jit_step") == "jit_step"
    tr = tm.Trace(device={"/device:TPU:0": sorted(ops)},
                  host=[(0, 130, "bench.execute#0")])
    red = tm.reduce(tr)
    assert red.busy_ns == 110
    assert [n for n, _ in red.top_ops] == ["%copy.3 = bf16[2]",
                                           "%fusion.2 = bf16[4,8]",
                                           "%dot.4 = f32[8,8]"]
