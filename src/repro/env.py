"""Typed registry of every ``REPRO_*`` environment knob.

This module is the ONE legal way to read a ``REPRO_*`` variable: each knob
declares its type, default, legal values and effect here, and every read
goes through :func:`get`, which validates at read time.  The static
analyzer (``python -m tools.analysis``, pass ``env-knobs``) flags any
direct ``os.environ`` access to a ``REPRO_*`` name outside this file, so a
new knob cannot ship without a registry entry — and therefore cannot ship
without validation or documentation (``python -m tools.analysis
--knob-table`` renders the README reference table from this registry).

Knobs are read lazily (at call time, not import time): tests monkeypatch
the environment and tools set knobs for subprocesses, so values are never
cached here.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Dict, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered environment variable.

    ``type`` is ``int``, ``bool`` or ``str``.  String knobs validate
    against ``choices`` (after mapping legacy spellings through
    ``aliases``); int knobs enforce ``minimum``.  ``legacy_name`` is a
    deprecated variable consulted (with a ``DeprecationWarning``) when the
    canonical name is unset.
    """

    name: str
    type: type
    default: Any
    doc: str
    choices: Optional[Tuple[str, ...]] = None
    minimum: Optional[int] = None
    aliases: Mapping[str, str] = dataclasses.field(default_factory=dict)
    legacy_name: Optional[str] = None

    def parse(self, raw: str) -> Any:
        """Validate + convert one raw environment string."""
        if self.type is int:
            try:
                v = int(raw)
            except ValueError:
                raise ValueError(
                    f"{self.name}={raw!r}: expected an integer") from None
            if self.minimum is not None and v < self.minimum:
                raise ValueError(
                    f"{self.name}={v}: must be >= {self.minimum}")
            return v
        if self.type is bool:
            lowered = raw.strip().lower()
            if lowered in ("1", "true"):
                return True
            if lowered in ("0", "false"):
                return False
            raise ValueError(
                f"{self.name}={raw!r}: expected one of 0, 1, false, true")
        v = self.aliases.get(raw, raw)
        if self.choices is not None and v not in self.choices:
            legal = ", ".join(self.choices)
            raise ValueError(
                f"{self.name}={raw!r} is not a legal value; "
                f"allowed: {legal}")
        return v

    def describe_values(self) -> str:
        """Human-readable value domain for the knob table."""
        if self.choices is not None:
            return ", ".join(self.choices)
        if self.type is bool:
            return "0, 1"
        if self.type is int and self.minimum is not None:
            return f"int >= {self.minimum}"
        return self.type.__name__


REGISTRY: Dict[str, Knob] = {}


def _register(**kw) -> Knob:
    knob = Knob(**kw)
    if knob.name in REGISTRY:
        raise ValueError(f"duplicate knob {knob.name}")
    REGISTRY[knob.name] = knob
    return knob


_register(
    name="REPRO_PAGED_ATTN_BACKEND", type=str, default="xla",
    choices=("xla", "pallas"),
    doc="Attention backend for the paged packed path: portable XLA "
        "key loop over the block tables, bounded by the longest live "
        "context, or the block-table Pallas kernels (native on TPU, "
        "interpret mode elsewhere).")
_register(
    name="REPRO_PALLAS_INTERPRET", type=str, default="auto",
    choices=("0", "1", "false", "true", "auto"),
    doc="Force the Pallas kernels' interpret mode (1/true) or native "
        "compilation (0/false); auto compiles on TPU and interprets "
        "elsewhere.")
_register(
    name="REPRO_PAGED_KV_PAGES", type=int, default=1, minimum=1,
    doc="Physical KV blocks fetched + folded per paged-kernel grid step.")
_register(
    name="REPRO_PAGED_KV_BUFFERS", type=int, default=2, minimum=1,
    doc="VMEM ring slots for the paged kernels' KV page DMAs (1 = serial "
        "fetch->compute, 2 = double-buffered, 4 = quad).")
_register(
    name="REPRO_PAGED_Q_BLOCK", type=int, default=128, minimum=1,
    doc="Query-tile rows for the paged chunked-prefill kernel (clamped "
        "against the chunk length).")
_register(
    name="REPRO_SCAN_UNROLL", type=bool, default=False,
    doc="Fully unroll the layer scan so compiled.cost_analysis() counts "
        "every layer (the roofline pass); the rolled scan is the "
        "deployable artifact.")
_register(
    name="REPRO_SHARD_KV", type=str, default="seq",
    choices=("seq", "hd", "none"),
    aliases={"1": "hd", "0": "none"},
    legacy_name="REPRO_SHARD_KV_HD",
    doc="GQA cache sharding when n_kv_heads doesn't divide the model "
        "axis: shard the sequence/block dim (seq, context-parallel "
        "decode), head_dim (hd), or replicate (none).")
_register(
    name="REPRO_DECODE_ACT_RESHARD", type=bool, default=True,
    doc="FSDP archs only: constrain decode-step layer-boundary "
        "activations to the d-model-sharded layout so per-layer "
        "collectives are O(activations) instead of an O(weights) "
        "all-gather.")
_register(
    name="REPRO_MOE_DISPATCH_SHARD", type=bool, default=True,
    doc="Shard the MoE dispatch buffer over the batch axes (0 restores "
        "the replicated baseline).")


def get(name: str) -> Any:
    """Read knob ``name`` from the environment: validated, typed, and
    falling back to the registered default (or the deprecated
    ``legacy_name`` spelling, with a ``DeprecationWarning``) when unset."""
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(
            f"{name} is not a registered REPRO_* knob; declare it in "
            f"repro/env.py (known: {sorted(REGISTRY)})")
    raw = os.environ.get(knob.name)
    if raw is None and knob.legacy_name is not None:
        raw = os.environ.get(knob.legacy_name)
        if raw is not None:
            warnings.warn(
                f"{knob.legacy_name} is deprecated; set {knob.name} "
                f"instead (legal values: {knob.describe_values()})",
                DeprecationWarning, stacklevel=2)
    if raw is None:
        return knob.default
    return knob.parse(raw)


def knob_table() -> list:
    """Rows (name, type, default, values, doc) for every registered knob,
    sorted by name — the source of the README reference table."""
    rows = []
    for name in sorted(REGISTRY):
        k = REGISTRY[name]
        default = {True: "1", False: "0"}.get(k.default, str(k.default))
        rows.append((k.name, k.type.__name__, default,
                     k.describe_values(), k.doc))
    return rows


def format_knob_table() -> str:
    """The knob reference as a markdown table (what ``python -m
    tools.analysis --knob-table`` prints and the README embeds)."""
    lines = ["| name | type | default | values | effect |",
             "|---|---|---|---|---|"]
    for name, typ, default, values, doc in knob_table():
        lines.append(f"| `{name}` | {typ} | `{default}` | {values} "
                     f"| {doc} |")
    return "\n".join(lines)
