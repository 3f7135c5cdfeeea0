"""End-to-end metrics of a window, on logs written by hand."""
from __future__ import annotations

import chipbench_tiny as T  # noqa: F401
import pytest

from chipbench import measure
from chipbench.driver import Log
from chipbench.traffic import Job


def _log(i, due, tokens, prompt=100, finished=None):
    return Log(job=Job(i, [0] * prompt, len(tokens), due), due=due,
               tokens=list(tokens), finished=finished,
               output=[1] * len(tokens))


def test_window_metrics_of_hand_written_logs():
    """Window [10, 20]: a request due before it whose tokens straddle its
    opening, one due inside it served at once, and one due inside it
    that has no token by its close."""
    logs = {0: _log(0, 8.0, [9.0, 11.0, 12.0, 14.0], finished=14.0),
            1: _log(1, 12.0, [12.5, 13.0, 13.5, 14.5, 19.0], prompt=300),
            2: _log(2, 16.0, [])}
    out = measure.end_to_end(logs, 10.0, 20.0, setup_s=3.0)
    # tokens inside the window: three of request 0, five of request 1
    assert out["out_tok_s"] == pytest.approx(0.8)
    assert out["setup_s"] == 3.0
    # TTFTs of the requests due in the window: 0.5 s, and 4 s waited
    assert out["ttft_p50_ms"] == pytest.approx(2250.0)
    assert out["ttft_per_ktok_ms"] == pytest.approx(4.5e6 / 400)
    # gaps inside the window: 1, 2 (request 0); .5, .5, 1, 4.5 (request 1)
    gaps = sorted([1.0, 2.0, 0.5, 0.5, 1.0, 4.5])
    for q in (50, 90, 99):
        assert out[f"tbt_p{q}_ms"] == pytest.approx(
            measure.percentile(gaps, q) * 1e3)
    assert out["tbt_p90_ms"] == pytest.approx(3250.0)
    # every request with work in the window ends with all it asked for
    assert measure.failures(logs, 10.0, 20.0) == 0
    logs[0].output.pop()
    assert measure.failures(logs, 10.0, 20.0) == 1
