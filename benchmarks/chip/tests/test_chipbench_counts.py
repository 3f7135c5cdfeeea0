"""Operation and byte counts against values worked by hand."""
from __future__ import annotations

import chipbench_tiny as T

from chipbench import counts

# tiny config: d 64, 4 query / 2 KV heads of 16, d_ff 128, 2 layers, V 256
HF = T.config()


def test_linear_attention_unembed():
    # per layer: q 64*64 + k,v 2*64*32 + o 64*64 + ffn 3*64*128 = 36,864
    assert counts.linear_flops_per_token(HF) == 2 * 36_864 * 2
    assert counts.attn_flops(HF, 1) == 4 * 4 * 16 * 2
    assert counts.unembed_flops(HF) == 2 * 64 * 256
    assert counts.chunk_keys(10, 4) == 11 + 12 + 13 + 14
    assert counts.chunk_keys(0, 1) == 1


def test_step_flops():
    # chunk of 4 after 10 (its last), one decode at position 5 (6 keys)
    got = counts.step_flops(HF, [(10, 4, True)], [5])
    assert got == 5 * 147_456 + 512 * (50 + 6) + 2 * 32_768
    # a chunk that does not end its prompt samples nothing
    assert counts.step_flops(HF, [(0, 4, False)], []) \
        == 4 * 147_456 + 512 * 10
    assert counts.step_flops(HF, [], []) == 0


def test_bytes():
    # layers (36,864 + two norms of 64) x 2, final norm, unembedding; bf16
    assert counts.weight_bytes(HF) == 2 * (2 * 36_992 + 64 + 64 * 256)
    tied_bias = T.config(qwen=True)      # tied, with Q/K/V bias
    assert counts.weight_bytes(tied_bias) == 2 * (
        2 * (36_992 + 64 + 2 * 32) + 64 + 64 * 256)
    assert counts.kv_bytes_per_token(HF) == 2 * 2 * 16 * 2 * 2
    assert counts.decode_step_bytes(HF, [5, 7]) == \
        counts.weight_bytes(HF) + 12 * 256 + 2 * 256
