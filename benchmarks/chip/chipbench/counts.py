"""Operations and bytes a packed step needs, counted from its plan.

What one token, one (query, key) pair or one sampled row costs belongs to
the model: the configuration's reference states it (``step_terms``), and
a ``Terms`` holds it.  What follows from a plan, whatever the model, is
here.

Counts are of the model's work on real tokens only: padding lanes, padded
chunk rows and the scratch slot count nothing.  A query at position p
reads p + 1 keys.  The unembedding counts for rows whose token is
sampled: every decode, and a chunk's last row when the chunk ends its
prompt.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple

Chunk = Tuple[int, int, bool]          # (start, length, is_last)


@dataclass(frozen=True)
class Terms:
    """One configuration's costs, as its reference counts them."""
    # every layer's matmuls for one token (sparse experts: the active ones)
    linear_flops_per_token: int
    # one (query, key) pair, summed over the attention layers
    attn_flops_per_pair: int
    # the unembedding of one sampled row
    unembed_flops: int
    # every weight a step reads once
    weight_bytes: int
    # state of one context position (keys and values), every layer
    state_bytes_per_token: int
    # state a request holds whatever its length (a recurrent state)
    state_bytes_per_request: int


def chunk_keys(start: int, n: int) -> int:
    """(query, key) pairs of a causal chunk of ``n`` tokens after ``start``."""
    return n * start + n * (n + 1) // 2


def step_flops(t: Terms, chunks: Iterable[Chunk],
               decodes: Sequence[int]) -> int:
    """One plan: ``chunks`` [(start, length, is_last)], ``decodes`` the
    context length of each real decode (the position its token goes to)."""
    chunks = list(chunks)
    tokens = sum(n for _, n, _ in chunks) + len(decodes)
    keys = sum(chunk_keys(s, n) for s, n, _ in chunks) \
        + sum(c + 1 for c in decodes)
    sampled = sum(1 for *_, last in chunks if last) + len(decodes)
    return (tokens * t.linear_flops_per_token + keys * t.attn_flops_per_pair
            + sampled * t.unembed_flops)


def decode_step_bytes(t: Terms, decodes: Sequence[int]) -> int:
    """The least bytes of a decode-only step: weights once, the state of
    every real context read, one new position written per decode, and each
    request's fixed state read and written."""
    return (t.weight_bytes + (sum(decodes) + len(decodes))
            * t.state_bytes_per_token
            + 2 * len(decodes) * t.state_bytes_per_request)
