"""The check that decides ``correct``: a sound run passes, and the control
and every planted fault under the timed path fail it.

At this test's tiny size (two layers of width 64, served in bf16) sound
runs read a widest logit gap of 0.015 at most and the control (the
reference in float8) 0.30 at least (CPU, seeds 21-23); the limit 0.05
lies between them.
"""
from __future__ import annotations

import json
import time

import chipbench_tiny as T
import jax
import pytest

from chipbench import harness

LIMIT = 0.05


def _run(seed, trace=False, hook=None, control=False):
    return harness.run(T.cell(logit_gap_max=LIMIT), seed, 2.0, trace,
                       t_proc=time.perf_counter(), devices=jax.devices(),
                       peaks=T.PEAKS, server_hook=hook, control=control)


def test_sound_run_is_correct():
    out = _run(41)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 20
    assert set(out["metrics"]) == {"ttft_per_ktok_ms", "tbt_p99_ms",
                                   "out_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "check"
    assert out["check"]["window_compiles_max"]["value"] == 0
    assert out["check"]["logit_gap_max"]["value"] <= LIMIT
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics_only():
    out = _run(42, trace=True)
    assert out["correct"] is True
    # the CPU has no device plane: only the plan counter has a reading
    assert set(out["metrics"]) == {"decode_lanes_used"}


def test_trace_covers_every_step_of_the_window(monkeypatch):
    """The profiler runs from before the window's first step until after
    its last, so a traced run holds device work whenever the window does."""
    from chipbench import measure
    from chipbench.driver import clock
    seen = {}
    start, stop, reading = harness.start_trace, jax.profiler.stop_trace, \
        measure.Reading

    def on_start(d):
        seen["start"] = clock()
        start(d)

    def on_stop():
        stop()
        seen["stop"] = clock()

    def on_reading(**kw):
        seen["steps"] = kw["steps"]
        return reading(**kw)

    monkeypatch.setattr(harness, "start_trace", on_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", on_stop)
    monkeypatch.setattr(measure, "Reading", on_reading)
    _run(43, trace=True)
    steps = seen["steps"]
    assert steps
    assert seen["start"] <= steps[0].plan[0]
    assert steps[-1].on_tokens[1] <= seen["stop"]


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_fails(seed):
    """The control in the program's place: the same run, not correct."""
    out = _run(seed, control=True)
    assert out["correct"] is False
    assert out["check"]["logit_gap_max"]["value"] > LIMIT


@pytest.mark.parametrize("fault", sorted(T.FAULTS))
def test_planted_fault_fails(fault):
    out = _run(31, hook=T.FAULTS[fault])
    assert out["correct"] is False
    assert out["check"]["logit_gap_max"]["value"] > LIMIT
