"""Plain reference of a Llama-architecture decoder with a bias on every
projection, as IBM's Granite code models publish it (``model_type``
``llama`` with ``attention_bias`` and ``mlp_bias``; Granite-8B-Code,
arXiv:2405.04324):

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                rotary embedding on q and k (rotate-half layout,
                inverse frequencies theta^(-2i / head_dim))
                a = softmax(q k^T / sqrt(head_dim), causal) v,
                    query head j reading key/value head j // (nq / nkv)
                x = x + (a Wo + bo)
                h = rmsnorm(x) * ln2
                x = x + ((silu(h Wg + bg) * (h Wu + bu)) Wd + bd)
    logits = (rmsnorm(x) * final_norm) W_unembed   (embed^T when tied)

``attention_bias`` puts a bias on Q, K, V and O (transformers'
``LlamaAttention``), ``mlp_bias`` on gate, up and down (``LlamaMLP``);
either may be false, and its biases are then absent.

Precision, blocking and the control are ``dense_gqa``'s, whose helpers
this module uses: float32 with matmuls at ``highest`` precision, one
sequence at a time, layer by layer under ``lax.scan``, attention in blocks
of query rows; ``control=True`` rounds every matmul input to float8 e4m3
with a scale and accumulates in float32 (the biases are added in float32
in both).  Weight names are the paths of the served program's parameter
tree; nothing of the program is imported but its config class in
``program_config``.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.spec import load_module

dense = load_module(Path(__file__).with_name("dense_gqa.py"),
                    "chipbench_granite_dense_gqa")

QUERY_BLOCK = dense.QUERY_BLOCK


def _sizes(hf: dict):
    d = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    return (d, nh, hf["num_key_value_heads"], hf.get("head_dim") or d // nh,
            hf["intermediate_size"], hf["num_hidden_layers"])


def _biases(hf: dict):
    return bool(hf.get("attention_bias")), bool(hf.get("mlp_bias"))


def _unbiased(hf: dict) -> dict:
    """The configuration without its biases, as ``dense_gqa`` reads it."""
    return {**hf, "attention_bias": False, "mlp_bias": False}


def weight_specs(hf: dict):
    """[(name, shape, kind)] of every weight; every bias has law ``bias``
    (std 0.1, so a bias the program drops moves the logits)."""
    d, nh, nkv, hd, ff, L = _sizes(hf)
    attn_b, mlp_b = _biases(hf)
    g = "groups/0/"
    specs = dense.weight_specs(_unbiased(hf))
    if attn_b:
        specs += [(g + "mixer/bq", (L, nh * hd), "bias"),
                  (g + "mixer/bk", (L, nkv * hd), "bias"),
                  (g + "mixer/bv", (L, nkv * hd), "bias"),
                  (g + "mixer/bo", (L, d), "bias")]
    if mlp_b:
        specs += [(g + "ffn/b_gate", (L, ff), "bias"),
                  (g + "ffn/b_up", (L, ff), "bias"),
                  (g + "ffn/b_down", (L, d), "bias")]
    return specs


def program_config(hf: dict):
    """The program's ModelConfig for a configuration file, checked against
    every published key that shapes what the model computes."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(**hf["program"])
    d, nh, nkv, hd, ff, L = _sizes(hf)
    attn_b, mlp_b = _biases(hf)
    want = {
        "family": "dense", "d_model": d, "n_layers": L, "n_heads": nh,
        "n_kv_heads": nkv, "head_dim": hd, "d_ff": ff,
        "vocab_size": hf["vocab_size"], "rope_theta": hf["rope_theta"],
        "norm_eps": hf["rms_norm_eps"],
        "tie_embeddings": hf["tie_word_embeddings"],
        # the gated FFN is the program's only for silu (SwiGLU)
        "act": "silu",
        "qkv_bias": attn_b, "o_bias": attn_b, "mlp_bias": mlp_b,
        "sliding_window": None,
    }
    bad = {k: (getattr(cfg, k, None), v) for k, v in want.items()
           if getattr(cfg, k, None) != v}
    if hf["hidden_act"] != "silu":
        bad["hidden_act"] = ("silu", hf["hidden_act"])
    if hf.get("rope_scaling") is not None:
        bad["rope_scaling"] = ("not served by the program",
                               hf["rope_scaling"])
    if hf.get("sliding_window") is not None \
            and hf.get("use_sliding_window", True):
        bad["sliding_window"] = ("not served by the program",
                                 hf["sliding_window"])
    if hf["model_type"] != "llama":
        bad["model_type"] = ("llama", hf["model_type"])
    if bad:
        raise ValueError(f"{hf['name']}: program config disagrees with "
                         f"the published keys (program, file): {bad}")
    return cfg


def step_terms(hf: dict) -> dict:
    """``chipbench.counts.Terms`` of this model served in bf16:
    ``dense_gqa``'s counts of the projections, attention, unembedding and
    KV, with every bias read once per step (2 bytes an element) and added
    once per token (one operation an element)."""
    d, nh, nkv, hd, ff, L = _sizes(hf)
    attn_b, mlp_b = _biases(hf)
    t = dense.step_terms(_unbiased(hf))
    bias = (nh * hd + 2 * nkv * hd + d) * attn_b + (2 * ff + d) * mlp_b
    t["linear_flops_per_token"] += bias * L
    t["weight_bytes"] += 2 * bias * L
    return t


def logits_at(w: dict, hf: dict, tokens, read_pos, *, control=False):
    """Logits [P, V] at positions ``read_pos`` [P] of the sequence
    ``tokens`` [T] (T a multiple of QUERY_BLOCK; padding after the real
    tokens does not reach earlier positions under the causal mask)."""
    d, nh, nkv, hd, ff, L = _sizes(hf)
    eps = hf["rms_norm_eps"]
    theta = hf["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    g = "groups/0/"
    layers = {k[len(g):]: a for k, a in w.items() if k.startswith(g)}
    mm, rms = dense._mm, dense._rms

    def proj(x, lw, name, bias):
        y = mm(x, lw[name], control)
        return y + lw[bias].astype(jnp.float32) if bias in lw else y

    def layer(x, lw):
        h = rms(x, lw["ln1"], eps)
        q = proj(h, lw, "mixer/wq", "mixer/bq")
        k = proj(h, lw, "mixer/wk", "mixer/bk")
        v = proj(h, lw, "mixer/wv", "mixer/bv")
        q = dense._rope(q.reshape(T, nh, hd), pos, theta)
        k = dense._rope(k.reshape(T, nkv, hd), pos, theta)
        a = dense._attention(q, k, v.reshape(T, nkv, hd))
        x = x + proj(a, lw, "mixer/wo", "mixer/bo")
        h = rms(x, lw["ln2"], eps)
        f = jax.nn.silu(proj(h, lw, "ffn/w_gate", "ffn/b_gate")) \
            * proj(h, lw, "ffn/w_up", "ffn/b_up")
        return x + proj(f, lw, "ffn/w_down", "ffn/b_down"), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = lax.scan(layer, x, layers)
    x = rms(x[read_pos], w["final_norm"], eps)
    head = w["embed"].T if hf["tie_word_embeddings"] else w["unembed"]
    return mm(x, head, control)
