"""The configuration check belongs to the configuration's reference:
``dense_gqa.program_config`` accepts what the program computes and
refuses the rest, and ``spec`` only finds the reference by name."""
from __future__ import annotations

import copy
import json
import re

import chipbench_tiny as T
import pytest

from chipbench import spec

dense_gqa = spec.load_module(T.BENCH / "references" / "dense_gqa.py")


def _config(name):
    return json.loads((T.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["mistral-7b-16l", "qwen2-0.5b"])
def test_dense_gqa_accepts_the_real_configurations(name):
    hf = _config(name)
    cfg = dense_gqa.program_config(hf)
    assert cfg.name == name and cfg.family == "dense"
    assert spec.program_config(hf) == cfg


def _set(**keys):
    def change(hf):
        hf.update(keys)
    return change


def _program(**keys):
    def change(hf):
        hf["program"].update(keys)
    return change


def _drop(key):
    def change(hf):
        hf.pop(key)
    return change


# (configuration, change, the key the refusal names)
REFUSED = {
    "attention_bias": ("mistral-7b-16l", _set(attention_bias=True),
                       "attention_bias"),
    "mlp_bias": ("mistral-7b-16l", _set(mlp_bias=True), "mlp_bias"),
    "rope_scaling": ("mistral-7b-16l",
                     _set(rope_scaling={"type": "linear", "factor": 2.0}),
                     "rope_scaling"),
    "window": ("mistral-7b-16l", _set(sliding_window=4096),
               "sliding_window"),
    "window_in_use": ("qwen2-0.5b", _set(use_sliding_window=True),
                      "sliding_window"),
    "window_by_default": ("qwen2-0.5b", _drop("use_sliding_window"),
                          "sliding_window"),
    "model_type": ("mistral-7b-16l", _set(model_type="gemma"),
                   "model_type"),
    "family": ("mistral-7b-16l", _program(family="moe", n_experts=8,
                                          top_k=2), "family"),
    "program_window": ("mistral-7b-16l", _program(sliding_window=4096),
                       "sliding_window"),
    "qkv_bias": ("qwen2-0.5b", _program(qkv_bias=False), "qkv_bias"),
    "no_qkv_bias": ("mistral-7b-16l", _program(qkv_bias=True), "qkv_bias"),
    "layers": ("mistral-7b-16l", _set(num_hidden_layers=32), "n_layers"),
    "d_ff": ("qwen2-0.5b", _program(d_ff=4096), "d_ff"),
    "head_dim": ("mistral-7b-16l", _set(head_dim=64), "head_dim"),
    "kv_heads": ("qwen2-0.5b", _set(num_key_value_heads=7), "n_kv_heads"),
    "rope_theta": ("qwen2-0.5b", _set(rope_theta=10000.0), "rope_theta"),
    "norm_eps": ("qwen2-0.5b", _set(rms_norm_eps=1e-5), "norm_eps"),
    "tied": ("mistral-7b-16l", _set(tie_word_embeddings=True),
             "tie_embeddings"),
    "act": ("mistral-7b-16l", _set(hidden_act="gelu"), "act"),
    "vocab": ("qwen2-0.5b", _set(vocab_size=151646), "vocab_size"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_dense_gqa_refuses_what_the_program_cannot_compute(case):
    name, change, key = REFUSED[case]
    hf = copy.deepcopy(_config(name))
    change(hf)
    with pytest.raises(ValueError, match=key):
        dense_gqa.program_config(hf)
    with pytest.raises(ValueError, match=key):
        spec.program_config(hf)


def test_spec_names_no_family_or_model_type():
    from repro.configs.base import FAMILIES
    text = (T.BENCH / "chipbench" / "spec.py").read_text()
    words = set(re.findall(r"[a-z0-9_]+", text.lower()))
    assert not words & (set(FAMILIES) | {"llama", "mistral", "qwen2"})
