"""The program's own spans and named scopes, read from a profiler trace.

``trace.load`` reads the benchmark's host spans (``bench.*``) and the
chip's operations.  The program marks its own layers on the same trace,
on the same clock:

* host spans ``repro.<layer>.<part>`` around the scheduler's and the
  engine's calls (``src/repro/obs.py``): ``repro.sched.next_plan``,
  ``repro.engine.pack``, ``repro.engine.collect``, ...;
* a ``jax.named_scope`` on each part of the packed step, which the
  compiler keeps in each operation's metadata (``op_name``).  Which names
  are scopes is read from the step's jaxpr (``named_scopes``), so a scope
  the program adds is found without an edit here.  A TPU trace's
  operation events carry the operation's HLO text but not its metadata,
  so the scopes are read from the compiled step's HLO text
  (``hlo_scopes``) and matched to the events by operation name.

This module reads both and reduces them, beside ``trace.reduce``:

* idle gaps labelled by the innermost span of either kind open at the
  gap's middle, so a gap inside ``bench.execute#k`` and
  ``repro.engine.pack`` reads ``engine.pack``;
* device seconds per scope path, every operation's time with its scope
  path, and the top operations with their path as a prefix
  (``kv_read|%fusion.229 = bf16[...]``);
* the readings that need the host spans: ``pack_ms_per_step``,
  ``idle_on_host_share`` and ``queue_wait_ms``.

A trace without program spans or scopes (a program that has none) gives
the labels ``trace.reduce`` gives, and readings of None.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import (Collection, Dict, Iterable, List, Optional, Sequence,
                    Set, Tuple)

from chipbench import trace as tm

PREFIX = "repro."

Span = Tuple[int, int, str]


@dataclass
class Program:
    # [(start_ns, end_ns, span name)] of the program's ``repro.*`` spans
    host: List[Span] = field(default_factory=list)
    # plane name -> [(start_ns, end_ns, op name, scope path or "")]
    device: Dict[str, List[Tuple[int, int, str, str]]] = field(
        default_factory=dict)


def scope_path(op_name: str, scopes: Collection[str]) -> str:
    """The names of ``scopes`` on an op name's path, outermost first and
    joined by '/' (``kv_read`` or ``kv_read/gather``), or ''.  A scope
    nested inside another keeps the outer one on its path, so a reader of
    the outer scope still counts it."""
    return "/".join(c for c in (op_name or "").split("/") if c in scopes)


def named_scopes(jaxpr) -> Set[str]:
    """Every name on the name stack of an equation of ``jaxpr`` or of a
    jaxpr nested in one (a scan's body, a branch, a kernel): the
    ``jax.named_scope``s the traced function puts on its operations.
    Names that are no identifier, such as the subscripts ``jnp.einsum``
    names its call by, are JAX's own and left out."""
    out: Set[str] = set()
    for e in jaxpr.eqns:
        out.update(s.name for s in e.source_info.name_stack.stack
                   if s.name.isidentifier())
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= named_scopes(sub)
    return out


_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def hlo_scopes(texts: Iterable[str],
               scopes: Collection[str]) -> Dict[str, str]:
    """{op label: scope path} of every operation scoped by one of
    ``scopes`` in the compiled modules' HLO text (``Compiled.as_text()``).
    An instruction takes its own op name's path (``scope_path``); a
    fusion without one takes the path most of the instructions of the
    computation it calls have.  A label that two modules give different
    paths (or one none) is left out."""
    out: Dict[str, str] = {}
    clash = set()
    for text in texts:
        called: Dict[str, List[str]] = defaultdict(list)
        instrs = []
        comp = ""
        for line in text.splitlines():
            if line.startswith(("%", "ENTRY ")):
                comp = line.split()[1 if line.startswith("ENTRY") else 0]
                comp = comp.lstrip("%")
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            own = _OP_NAME.search(rest)
            scope = scope_path(own.group(1), scopes) if own else ""
            if scope:
                called[comp].append(scope)
            calls = _CALLS.search(rest)
            instrs.append((name, rest, scope, calls and calls.group(1)))
        for name, rest, scope, calls in instrs:
            if not scope and calls and called.get(calls):
                found = called[calls]
                scope = max(dict.fromkeys(found), key=found.count)
            key = tm.op_label(f"%{name} = {rest}")
            if out.get(key, scope) != scope:
                clash.add(key)
            out[key] = scope
    return {k: v for k, v in out.items() if v and k not in clash}


def step_scopes(engine) -> Dict[str, str]:
    """{op label: scope path} of the engine's two packed-step shapes,
    hybrid and decode-only, each traced for its named scopes and compiled
    for its HLO text.  Compiled afresh, they are the programs the engine runs only
    where those were compiled afresh too: the persistent compilation
    cache's key leaves metadata out, so a program cached from code
    without the scopes comes back without them."""
    names: Set[str] = set()
    texts = []
    for pad_chunk in (True, False):
        pk = engine._pack(None, [], pad_chunk=pad_chunk)
        traced = engine._step.trace(engine.params, pk, engine.cache,
                                    engine._key)
        names |= named_scopes(traced.jaxpr.jaxpr)
        texts.append(traced.lower().compile().as_text())
    return hlo_scopes(texts, names)


def load(path: str, scopes: Optional[Dict[str, str]] = None) -> Program:
    """The program's spans and the chip's operations of a trace, each
    operation with its scope path from ``scopes`` (``hlo_scopes``),
    matched by its label (name and shape)."""
    import jax
    scopes = scopes or {}
    pd = jax.profiler.ProfileData.from_file(path)
    prog = Program()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == tm.OPS_LINE:
                    ops += [(int(e.start_ns),
                             int(e.start_ns + e.duration_ns), e.name,
                             scopes.get(tm.op_label(e.name), ""))
                            for e in line.events]
            if ops:
                prog.device[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                prog.host += [(int(e.start_ns),
                               int(e.start_ns + e.duration_ns), e.name)
                              for e in line.events
                              if e.name.startswith(PREFIX)]
    prog.host.sort()
    return prog


# ---------------------------------------------------------------- labels
def span_label(name: str) -> str:
    """'bench.execute#3' -> 'execute'; 'repro.engine.pack' -> 'engine.pack'."""
    if name.startswith(PREFIX):
        return name[len(PREFIX):]
    return tm.span_kind(name)


def innermost(spans: Sequence[Span], ts: Sequence[int]) -> List[Optional[str]]:
    """For each time in ``ts``, the name of the shortest span open at it
    (``a <= t <= b``), or None.  Spans of one thread nest, so a sweep with
    a stack of open spans finds it."""
    order = sorted(range(len(ts)), key=lambda i: ts[i])
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out: List[Optional[str]] = [None] * len(ts)
    stack: List[Span] = []
    i = 0
    for j in order:
        t = ts[j]
        while i < len(spans) and spans[i][0] <= t:
            stack.append(spans[i])
            i += 1
        open_ = [s for s in stack if s[1] >= t]
        stack = open_
        if open_:
            out[j] = min(open_, key=lambda s: s[1] - s[0])[2]
    return out


def labelled_gaps(tr: tm.Trace, prog: Program,
                  red: tm.Reduced) -> List[Tuple[str, float]]:
    """Every idle gap of every chip in the traced window, longest first:
    (label, seconds); the label is the innermost span of either kind open
    at the gap's middle ('none' outside every span)."""
    a, b = red.window
    gaps = [(g1 - g0, (g0 + g1) // 2)
            for m in red.merged.values() for g0, g1 in tm.gaps(m, a, b)]
    gaps.sort(key=lambda g: -g[0])
    names = innermost(tr.host + prog.host, [mid for _, mid in gaps])
    return [(span_label(n) if n else "none", g * 1e-9)
            for (g, _), n in zip(gaps, names)]


def idle_by_label(gaps: Iterable[Tuple[str, float]],
                  chips: int) -> Dict[str, float]:
    """Idle seconds summed per label, mean over chips, largest first."""
    out: Dict[str, float] = defaultdict(float)
    for label, s in gaps:
        out[label] += s / max(chips, 1)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ------------------------------------------------------------ operations
def _leaf_times(prog: Program, window: tm.Interval):
    """(scope path, op label, ns inside the window) of every innermost
    op."""
    a, b = window
    for ops in prog.device.values():
        for s, e, i in tm.leaves([(s, e, i)
                                  for i, (s, e, _, _) in enumerate(ops)]):
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                yield ops[i][3], tm.op_label(ops[i][2]), hi - lo


def device_by_scope(prog: Program, window: tm.Interval) -> Dict[str, float]:
    """Device seconds of innermost operations per scope path ('none' for
    ops without one), mean over chips, largest first."""
    out: Dict[str, float] = defaultdict(float)
    for scope, _, ns in _leaf_times(prog, window):
        out[scope or "none"] += ns
    chips = max(len(prog.device), 1)
    return {k: v * 1e-9 / chips
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def top_ops(prog: Program, window: tm.Interval,
            top: int = 10) -> List[Tuple[str, float]]:
    """``trace.reduce``'s top operations, each label prefixed by its scope
    path (``path|label``) where the op has one."""
    out: Dict[str, float] = defaultdict(float)
    for scope, label, ns in _leaf_times(prog, window):
        out[f"{scope}|{label}" if scope else label] += ns
    chips = max(len(prog.device), 1)
    return [(n, t * 1e-9 / chips)
            for n, t in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def op_times(prog: Program,
             window: tm.Interval) -> List[Tuple[str, str, float]]:
    """(op label, scope path or '', seconds) of every innermost operation
    in the window, summed per label, mean over chips, longest first."""
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for scope, label, ns in _leaf_times(prog, window):
        out[label, scope] += ns
    chips = max(len(prog.device), 1)
    return [(label, scope, t * 1e-9 / chips) for (label, scope), t
            in sorted(out.items(), key=lambda kv: -kv[1])]


# -------------------------------------------------------------- readings
def pack_ms_per_step(tr: tm.Trace, prog: Program,
                     window: tm.Interval) -> Optional[float]:
    """Milliseconds of ``repro.engine.pack`` per traced step (its packed
    sub-steps summed), mean over the steps whose execute span lies in the
    window."""
    packs = [(a, b) for a, b, n in prog.host if n == PREFIX + "engine.pack"]
    steps = [sp["execute"] for sp in tm.step_spans(tr).values()
             if "execute" in sp and window[0] <= sp["execute"][0]
             and sp["execute"][1] <= window[1]]
    if not packs or not steps:
        return None
    total = sum(b - a for a, b in packs
                for s0, s1 in steps if s0 <= a and b <= s1)
    return total * 1e-6 / len(steps)


def idle_on_host_share(prog: Program, red: tm.Reduced) -> Optional[float]:
    """Share of the traced window in which the device is idle inside a
    program span: the chip waiting on the program's host code (the
    driver's ``wait`` for arrivals is outside every program span)."""
    a, b = red.window
    spans = tm.merge([(max(s, a), min(e, b)) for s, e, _ in prog.host])
    if not spans or b <= a:
        return None
    idle = sum((e - s) - red.device_ns(s, e) for s, e in spans)
    return 100.0 * idle / (b - a)


def queue_wait_ms(requests: Iterable, w0: float,
                  w1: float) -> Optional[float]:
    """Mean wait from arrival to the first scheduled chunk of the requests
    due in ``[w0, w1)``, on the scheduler's clock (``arrival_time``); one
    not scheduled by ``w1`` counts with its wait so far."""
    due = [r for r in requests if w0 <= r.arrival_time < w1]
    if not due or not hasattr(due[0], "first_scheduled"):
        return None
    waits = [(r.first_scheduled if r.first_scheduled is not None
              and r.first_scheduled <= w1 else w1) - r.arrival_time
             for r in due]
    return 1e3 * sum(waits) / len(waits)
