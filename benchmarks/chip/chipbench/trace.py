"""Reduction of a profiler trace to device busy time, per span.

A trace is read from the ``.xplane.pb`` that ``jax.profiler`` writes:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:<kind>:<n>`` plane (one plane per chip);
* host spans: the events on the host plane whose names start with
  ``bench.`` — the ``TraceAnnotation``s the benchmark's driver places
  around each call into the program, named ``bench.<what>#<step>``.

Both are on the profiler's one clock, in nanoseconds.  Busy time is the
union of a chip's operation intervals; a span's device time is the part
of that union inside the span; idle gaps are the holes in the union,
each labelled by the innermost benchmark span open on the host at the
gap's middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)


@dataclass
class Trace:
    # plane name -> [(start_ns, end_ns, op name)]
    device: Dict[str, List[Tuple[int, int, str]]] = field(default_factory=dict)
    # [(start_ns, end_ns, span name)]
    host: List[Tuple[int, int, str]] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                             e.name) for e in line.events]
            if ops:
                tr.device[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.host.append((int(e.start_ns),
                                        int(e.start_ns + e.duration_ns),
                                        e.name))
    tr.host.sort()
    return tr


def op_label(name: str) -> str:
    """'%copy.113 = bf16[15,272]{...} copy(...)' -> '%copy.113 = bf16[15,272]'."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:120]
    return (head + " = " + rest.split("{", 1)[0].split(" ", 1)[0])[:120]


def leaves(ops: Sequence[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """The operations that hold no other one: a loop's own event spans
    the operations of its body on the same line."""
    out = []
    for i, (s, e, name) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[0] < e and nxt[1] <= e:
            continue
        out.append((s, e, name))
    return out


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(merged: Sequence[Interval], a: int, b: int) -> int:
    """Length of ``merged`` (disjoint, sorted) inside [a, b]."""
    if b <= a or not merged:
        return 0
    i = max(bisect.bisect_right(merged, (a, a)) - 1, 0)
    total = 0
    while i < len(merged) and merged[i][0] < b:
        lo, hi = max(merged[i][0], a), min(merged[i][1], b)
        if hi > lo:
            total += hi - lo
        i += 1
    return total


def gaps(merged: Sequence[Interval], a: int, b: int) -> List[Interval]:
    """Holes of ``merged`` inside [a, b]."""
    out, t = [], a
    for lo, hi in merged:
        if hi <= a:
            continue
        if lo >= b:
            break
        if lo > t:
            out.append((t, lo))
        t = max(t, hi)
    if t < b:
        out.append((t, b))
    return out


def span_kind(name: str) -> str:
    """'bench.execute#12' -> 'execute'."""
    return name[len(SPAN_PREFIX):].split("#", 1)[0]


def span_step(name: str) -> Optional[int]:
    parts = name.split("#", 1)
    return int(parts[1]) if len(parts) == 2 and parts[1].isdigit() else None


def label_at(host: Sequence[Tuple[int, int, str]], t: int) -> str:
    """The innermost benchmark span open at ``t``, or 'none'."""
    best = None
    for a, b, name in host:
        if a > t:
            break
        if b >= t and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return span_kind(best[2]) if best else "none"


@dataclass
class Reduced:
    window: Interval                      # traced window, ns
    chips: int
    busy_ns: float                        # union of ops, mean over chips
    merged: Dict[str, List[Interval]]     # per chip
    top_ops: List[Tuple[str, float]]      # innermost ops: name, seconds
    #                                       (mean over chips)
    idle_gaps: List[Tuple[str, float]]    # label, seconds (longest first)
    collective_ns: float                  # mean over chips

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def device_ns(self, a: int, b: int) -> float:
        """Device busy time inside [a, b], mean over chips."""
        return sum(overlap(m, a, b) for m in self.merged.values()) \
            / max(self.chips, 1)


def reduce(tr: Trace, window: Optional[Interval] = None,
           top: int = 10) -> Optional[Reduced]:
    """Busy time, top operations and idle gaps of ``tr`` in ``window``
    (default: from the first benchmark span's start to the last one's
    end).  None when the trace holds no device operation or no span."""
    if not tr.device or (window is None and not tr.host):
        return None
    if window is None:
        window = (tr.host[0][0], max(b for _, b, _ in tr.host))
    a, b = window
    chips = len(tr.device)
    merged, busy, coll = {}, 0.0, 0.0
    by_name: Dict[str, float] = defaultdict(float)
    all_gaps: List[Tuple[int, int]] = []
    for plane, ops in tr.device.items():
        m = merge([(s, e) for s, e, _ in ops])
        merged[plane] = m
        busy += overlap(m, a, b)
        for s, e, name in leaves(ops):
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                by_name[op_label(name)] += (hi - lo)
                if COLLECTIVE.search(name):
                    coll += hi - lo
        all_gaps += [(g1 - g0, (g0 + g1) // 2) for g0, g1 in gaps(m, a, b)]
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    all_gaps.sort(key=lambda g: -g[0])
    return Reduced(
        window=window, chips=chips, busy_ns=busy / chips, merged=merged,
        top_ops=[(n, t * 1e-9 / chips) for n, t in top_ops],
        idle_gaps=[(label_at(tr.host, mid), g * 1e-9)
                   for g, mid in all_gaps[:top]],
        collective_ns=coll / chips)


def step_spans(tr: Trace) -> Dict[int, Dict[str, Interval]]:
    """{step: {span kind: (start, end)}} of the benchmark's spans."""
    out: Dict[int, Dict[str, Interval]] = defaultdict(dict)
    for a, b, name in tr.host:
        k = span_step(name)
        if k is not None:
            out[k][span_kind(name)] = (a, b)
    return dict(out)
