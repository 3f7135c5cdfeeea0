"""Operations and bytes a packed step needs, counted from its shapes.

Counts are of the model's work on real tokens only: padding lanes, padded
chunk rows and the scratch slot count nothing.  A matmul of [m, k] by
[k, n] is 2*m*k*n operations.  Attention of a query at position p reads
p + 1 keys: 2*head_dim operations for its score and 2*head_dim for its
share of the weighted sum, per query head.

The unembedding counts for rows whose token is sampled: every decode, and
a chunk's last row when the chunk ends its prompt.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

Chunk = Tuple[int, int, bool]          # (start, length, is_last)


def _dims(hf: dict):
    d = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    hd = hf.get("head_dim") or d // nh
    return d, nh, hf["num_key_value_heads"], hd


def linear_flops_per_token(hf: dict) -> int:
    """Projections and the gated FFN of every layer, for one token."""
    d, nh, nkv, hd = _dims(hf)
    ff = hf["intermediate_size"]
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff
    return 2 * per_layer * hf["num_hidden_layers"]


def attn_flops(hf: dict, n_keys: int) -> int:
    """Attention over ``n_keys`` (query, key) pairs, every layer."""
    d, nh, _, hd = _dims(hf)
    return 4 * nh * hd * n_keys * hf["num_hidden_layers"]


def unembed_flops(hf: dict) -> int:
    return 2 * hf["hidden_size"] * hf["vocab_size"]


def chunk_keys(start: int, n: int) -> int:
    """(query, key) pairs of a causal chunk of ``n`` tokens after ``start``."""
    return n * start + n * (n + 1) // 2


def step_flops(hf: dict, chunks: Iterable[Chunk],
               decodes: Sequence[int]) -> int:
    """One plan: ``chunks`` [(start, length, is_last)], ``decodes`` the
    context length of each real decode (the position its token goes to)."""
    chunks = list(chunks)
    tokens = sum(n for _, n, _ in chunks) + len(decodes)
    keys = sum(chunk_keys(s, n) for s, n, _ in chunks) \
        + sum(c + 1 for c in decodes)
    sampled = sum(1 for *_, last in chunks if last) + len(decodes)
    return (tokens * linear_flops_per_token(hf) + attn_flops(hf, keys)
            + sampled * unembed_flops(hf))


def weight_bytes(hf: dict, itemsize: int = 2) -> int:
    """Every weight a step reads once: the layers, the final norm and the
    unembedding (the embedding table when tied).  Embedding rows gathered
    for the step's tokens are left out (a few KB)."""
    d, nh, nkv, hd = _dims(hf)
    ff = hf["intermediate_size"]
    per_layer = (d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff
                 + 2 * d)
    if hf.get("model_type") == "qwen2" or hf.get("attention_bias"):
        per_layer += nh * hd + 2 * nkv * hd
    n = per_layer * hf["num_hidden_layers"] + d + d * hf["vocab_size"]
    return n * itemsize


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    _, _, nkv, hd = _dims(hf)
    return 2 * nkv * hd * itemsize * hf["num_hidden_layers"]


def decode_step_bytes(hf: dict, decodes: Sequence[int],
                      itemsize: int = 2) -> int:
    """The least bytes of a decode-only step: weights once, the keys and
    values of every real context read, and one new position written per
    decode."""
    kv = kv_bytes_per_token(hf, itemsize)
    return (weight_bytes(hf, itemsize) + sum(decodes) * kv
            + len(decodes) * kv)
