"""End-to-end metrics of a window, and what per-layer readers are given.

The window is ``[w0, w1]`` on the host's clock.  A request's TTFT runs
from its due time to its first token; a request due in the window with no
first token by ``w1`` counts with the time it has waited until then.

* ``ttft_p50_ms``, ``ttft_p90_ms``: percentiles of the TTFT of every
  request due in the window;
* ``ttft_per_ktok_ms``: the TTFTs of those requests summed, over their
  prompt tokens summed, per thousand prompt tokens;
* ``tbt_p50_ms``, ``tbt_p90_ms``, ``tbt_p99_ms``: percentiles of every
  gap between two consecutive tokens of one request, both inside the
  window;
* ``out_tok_s``: tokens returned inside the window over its length.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from chipbench.counts import Terms
from chipbench.driver import Log, Step
from chipbench.traffic import percentile


def window_logs(logs: Dict[int, Log], w0: float, w1: float) -> List[Log]:
    """Requests with work in the window: due before it closes and not
    finished before it opened."""
    return [g for g in logs.values()
            if g.due < w1 and (g.finished is None or g.finished >= w0)]


def due_in(logs: Dict[int, Log], w0: float, w1: float) -> List[Log]:
    return [g for g in logs.values() if w0 <= g.due < w1]


def ttft(g: Log, w1: float) -> float:
    first = g.tokens[0] if g.tokens and g.tokens[0] <= w1 else w1
    return first - g.due


def ttfts(logs: Dict[int, Log], w0: float, w1: float) -> List[float]:
    return [ttft(g, w1) for g in due_in(logs, w0, w1)]


def tbts(logs: Dict[int, Log], w0: float, w1: float) -> List[float]:
    out = []
    for g in logs.values():
        t = [x for x in g.tokens if w0 <= x <= w1]
        out += [b - a for a, b in zip(t, t[1:])]
    return out


def out_tokens(logs: Dict[int, Log], w0: float, w1: float) -> int:
    return sum(1 for g in logs.values() for x in g.tokens if w0 <= x <= w1)


def end_to_end(logs: Dict[int, Log], w0: float, w1: float,
               setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric this benchmark knows, by name."""
    out = {"setup_s": setup_s,
           "out_tok_s": out_tokens(logs, w0, w1) / (w1 - w0)}
    due = due_in(logs, w0, w1)
    if due:
        t = [ttft(g, w1) for g in due]
        out["ttft_p50_ms"] = percentile(t, 50) * 1e3
        out["ttft_p90_ms"] = percentile(t, 90) * 1e3
        out["ttft_per_ktok_ms"] = sum(t) * 1e6 / sum(
            len(g.job.prompt) for g in due)
    gaps = tbts(logs, w0, w1)
    if gaps:
        out["tbt_p50_ms"] = percentile(gaps, 50) * 1e3
        out["tbt_p90_ms"] = percentile(gaps, 90) * 1e3
        out["tbt_p99_ms"] = percentile(gaps, 99) * 1e3
    return out


def failures(logs: Dict[int, Log], w0: float, w1: float) -> int:
    """Requests of the window the scheduler rejected, or that ended with
    fewer tokens than asked."""
    return sum(1 for g in window_logs(logs, w0, w1)
               if g.rejected or (g.finished is not None
                                 and len(g.output) < g.job.max_new_tokens))


@dataclass
class Reading:
    """What a per-layer metric's reader is given."""
    hf: dict                        # the configuration file
    terms: Terms                    # its reference's costs (counts.Terms)
    decode_lanes: int               # the engine's D
    chips: int
    peaks: dict                     # peaks.json entry of this device
    steps: List[Step]               # every step that started in the window
    traced: Optional[object] = None     # trace.Reduced, traced runs only
    # step index -> {span kind: (start_ns, end_ns)} on the trace's clock
    spans: Dict[int, Dict[str, tuple]] = field(default_factory=dict)
    # traced runs: device seconds in the traced window per scope path of
    # the packed step's named scopes, outermost first ('kv_read',
    # 'kv_read/gather'; 'none' for operations outside every scope), and
    # (operation, scope path or '', seconds) of every operation, longest
    # first; both mean over the chips (chipbench.program)
    scope_s: Dict[str, float] = field(default_factory=dict)
    ops: List[Tuple[str, str, float]] = field(default_factory=list)

    def under(self, scopes) -> float:
        """Device seconds of the traced operations under any of ``scopes``,
        at any depth of their scope path."""
        want = set(scopes)
        return sum(s for path, s in self.scope_s.items()
                   if want & set(path.split("/")))

    def traced_steps(self) -> List[Step]:
        """Steps whose execute span is inside the traced window."""
        if self.traced is None:
            return []
        a, b = self.traced.window
        return [s for s in self.steps
                if "execute" in self.spans.get(s.idx, {})
                and a <= self.spans[s.idx]["execute"][0]
                and self.spans[s.idx]["execute"][1] <= b]

    def device_s(self, step: Step) -> float:
        """Device busy seconds inside the step's execute span."""
        a, b = self.spans[step.idx]["execute"]
        return self.traced.device_ns(a, b) * 1e-9

    def span_s(self, step: Step, kind: str) -> float:
        a, b = self.spans[step.idx][kind]
        return (b - a) * 1e-9
