"""Host spans on the profiler's clock.

Every layer boundary of the served path (scheduler, engine host path) is a
``jax.profiler.TraceAnnotation`` named ``repro.<layer>.<part>``, so a
profile of a serving run puts the host's time and the device's operations
on one clock, and an idle gap of the device can be read as what the host
was doing in it.  Inside the packed step the layers are ``jax.named_scope``
blocks instead (``embed``, ``qkv``, ``kv_write``, ``kv_read``, ``attn``,
``o_proj``, ``ffn``, ``kv_carry``, ``unembed``, ``sample``): they only name
the compiled operations and never change the program.

A span costs about half a microsecond when no profiler is running, so the
spans are always there; they sit on layer boundaries, never per token.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``, as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def spanned(name: str):
    """Decorator: every call of the function is the span ``repro.<name>``."""
    return functools.partial(jax.profiler.annotate_function,
                             name=PREFIX + name)
