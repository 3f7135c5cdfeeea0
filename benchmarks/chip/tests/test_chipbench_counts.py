"""Operation and byte counts against values worked by hand, and the real
configurations' counts pinned to the dense formulas as they stood before
the reference took them over."""
from __future__ import annotations

import json

import chipbench_tiny as T
import pytest

from chipbench import counts, measure, spec
from chipbench.driver import Step

# tiny config: d 64, 4 query / 2 KV heads of 16, d_ff 128, 2 layers, V 256
HF = T.config()


def _terms(hf):
    return counts.Terms(**spec.reference(hf).step_terms(hf))


def test_linear_attention_unembed():
    t = _terms(HF)
    # per layer: q 64*64 + k,v 2*64*32 + o 64*64 + ffn 3*64*128 = 36,864
    assert t.linear_flops_per_token == 2 * 36_864 * 2
    assert t.attn_flops_per_pair == 4 * 4 * 16 * 2
    assert t.unembed_flops == 2 * 64 * 256
    assert counts.chunk_keys(10, 4) == 11 + 12 + 13 + 14
    assert counts.chunk_keys(0, 1) == 1


def test_step_flops():
    t = _terms(HF)
    # chunk of 4 after 10 (its last), one decode at position 5 (6 keys)
    got = counts.step_flops(t, [(10, 4, True)], [5])
    assert got == 5 * 147_456 + 512 * (50 + 6) + 2 * 32_768
    # a chunk that does not end its prompt samples nothing
    assert counts.step_flops(t, [(0, 4, False)], []) \
        == 4 * 147_456 + 512 * 10
    assert counts.step_flops(t, [], []) == 0


def test_bytes():
    t = _terms(HF)
    # layers (36,864 + two norms of 64) x 2, final norm, unembedding; bf16
    assert t.weight_bytes == 2 * (2 * 36_992 + 64 + 64 * 256)
    tied_bias = _terms(T.config(qwen=True))     # tied, with Q/K/V bias
    assert tied_bias.weight_bytes == 2 * (
        2 * (36_992 + 64 + 2 * 32) + 64 + 64 * 256)
    assert t.state_bytes_per_token == 2 * 2 * 16 * 2 * 2
    assert t.state_bytes_per_request == 0
    assert counts.decode_step_bytes(t, [5, 7]) == \
        t.weight_bytes + 12 * 256 + 2 * 256


def test_fixed_request_state_is_read_and_written_per_decode():
    t = counts.Terms(linear_flops_per_token=1, attn_flops_per_pair=1,
                     unembed_flops=1, weight_bytes=1000,
                     state_bytes_per_token=0, state_bytes_per_request=64)
    assert counts.decode_step_bytes(t, [5, 7, 9]) == 1000 + 2 * 3 * 64


# (linear per token, attention per pair, unembed per row, weight bytes,
# state bytes per token, step_flops of PLAN, decode_step_bytes of DECODES),
# from the dense formulas as they stood in chipbench/counts.py
PINNED = {
    "mistral-7b-16l": (6979321856, 262144, 268435456, 7248027648, 65536,
                       3681040728064, 7563124736),
    "qwen2-0.5b": (715653120, 86016, 272269312, 988065536, 12288,
                   397743259648, 1047146240),
}
PLAN = ([(1000, 256, True), (0, 256, False)], [10, 4095, 700])
DECODES = [10, 4095, 700]


def _config(name):
    return json.loads((T.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PINNED))
def test_real_configurations_count_as_before(name):
    t = _terms(_config(name))
    assert (t.linear_flops_per_token, t.attn_flops_per_pair,
            t.unembed_flops, t.weight_bytes, t.state_bytes_per_token,
            counts.step_flops(t, *PLAN),
            counts.decode_step_bytes(t, DECODES)) == PINNED[name]
    assert t.state_bytes_per_request == 0


class _Traced:
    """Device busy time of 1 ms in every execute span."""
    window = (0, 10 ** 12)

    def device_ns(self, a, b):
        return 1e6


@pytest.mark.parametrize("name", sorted(PINNED))
def test_real_configurations_step_readers_as_before(name):
    """step_mfu and step_hbm_share.decode on one hybrid and one
    decode-only step of 1 ms each, against the pinned counts."""
    hf = _config(name)
    steps = [Step(0, (0, 0), (0, 0), (0, 0), *PLAN),
             Step(1, (0, 0), (0, 0), (0, 0), [], DECODES)]
    r = measure.Reading(
        hf=hf, terms=_terms(hf), decode_lanes=7, chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        steps=steps, traced=_Traced(),
        spans={0: {"execute": (10, 20)}, 1: {"execute": (30, 40)}})
    flops, need = PINNED[name][5], PINNED[name][6]
    flops_decode = (3 * PINNED[name][0]
                    + (11 + 4096 + 701) * PINNED[name][1]
                    + 3 * PINNED[name][2])
    mfu = spec.load_module(T.BENCH / "metrics" / "step_mfu.py").read
    hbm = spec.load_module(
        T.BENCH / "metrics" / "step_hbm_share.decode.py").read
    # the readers' own arithmetic, as it stood, on the pinned counts
    busy = 1e6 * 1e-9
    assert mfu(r) == 100.0 * (flops + flops_decode) / (
        (busy + busy) * 197e12 * 1)
    assert hbm(r) == 100.0 * need / (busy * 819e9 * 1)
