"""The program's spans and named scopes: a span is a profiler annotation
named ``repro.<name>``, and the packed step's named scopes name its
operations without changing the compiled program."""
import contextlib
import re

import jax

from repro import obs
from repro.configs import get_config
from repro.core.engine import DecodeWork, Engine

_META = re.compile(r",? metadata=\{[^}]*\}")
# the source tables (FileNames ... StackFrames) between the module's
# header and its first computation
_TABLES = re.compile(r"\nFileNames\n.*?\n(?=%|ENTRY )", re.S)
_NAME = re.compile(r"%[\w.\-]+")
# numbered names outside instruction operands: parameters in signatures
_NUMBERED = re.compile(r"(?<![\w.%])([A-Za-z_][\w-]*)\.(\d+)\b")


def program_text(hlo: str) -> str:
    """Compiled HLO text less its metadata (each instruction's
    ``metadata={...}`` and the source tables it points into), with every
    name of an instruction or computation replaced by its order of first
    appearance: the TPU compiler names some instructions after their op
    names, so only the program, not its names, is compared."""
    text = _META.sub("", _TABLES.sub("\n", hlo))
    names, params = {}, {}
    text = _NAME.sub(lambda m: names.setdefault(m.group(0),
                                                f"%{len(names)}"), text)
    return _NUMBERED.sub(lambda m: params.setdefault(
        m.group(0), f"{m.group(1)}.{len(params)}"), text)


def _cfg():
    import dataclasses
    return dataclasses.replace(
        get_config("tinyllama-1.1b").reduced(), n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)


def compiled_steps(place=None):
    """HLO text of the hybrid and decode-only packed steps of a fresh
    paged engine, compiled for this backend or, given a sharding on a
    described device (``place``), for that device."""
    cfg = _cfg()
    from repro.models import build_model
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=3, max_len=64, chunk_size=16,
                 decode_slots=2, paged=True, block_size=8)
    eng.add_request(7)
    out = []
    for pad_chunk in (True, False):
        pk = eng._pack(None, [DecodeWork(7, 3, 5)], pad_chunk=pad_chunk)
        args = (eng.params, pk, eng.cache, eng._key)
        if place is not None:
            args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=place), args)
        out.append(eng._step.lower(*args).compile().as_text())
    return out


def assert_scopes_change_only_metadata(monkeypatch, place=None):
    """Both compiled step shapes are the same program with and without
    the named scopes; with them, the op names carry every scope of the
    paged xla path."""
    scoped = compiled_steps(place)
    names = set(re.findall(r'op_name="([^"]*)"', "".join(scoped)))
    for scope in ("embed", "qkv", "kv_write", "kv_read", "attn", "o_proj",
                  "ffn", "kv_carry", "unembed", "sample"):
        assert any(f"/{scope}/" in n or n.startswith(f"{scope}/")
                   for n in names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_steps(place)
    assert not any("/kv_read/" in n for n in
                   re.findall(r'op_name="([^"]*)"', "".join(plain)))
    for a, b in zip(scoped, plain):
        assert a != b                                 # op names differ
        assert program_text(a) == program_text(b)     # nothing else
        assert program_text(a).count("\n") > 100


def test_span_is_a_profiler_annotation_under_the_program_prefix():
    assert isinstance(obs.span("engine.pack"), jax.profiler.TraceAnnotation)
    calls = []

    @obs.spanned("engine.pack")
    def f(x):
        """doc"""
        calls.append(x)
        return x + 1

    assert f(1) == 2 and calls == [1]
    assert f.__name__ == "f" and f.__doc__ == "doc"


def test_named_scopes_change_only_metadata(monkeypatch):
    """The scopes name operations and change no compiled program (on the
    TPU compiler too: ``tests/test_tpu_compile.py``)."""
    assert_scopes_change_only_metadata(monkeypatch)
