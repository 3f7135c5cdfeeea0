"""Plain reference of a dense decoder with grouped-query attention.

Covers the Llama architecture (Mistral-7B) and Qwen2 (bias on the Q, K
and V projections, tied embeddings), as their published descriptions give
it:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
                rotary embedding on q and k (rotate-half layout,
                inverse frequencies theta^(-2i / head_dim))
                a = softmax(q k^T / sqrt(head_dim), causal) v,
                    query head j reading key/value head j // (nq / nkv)
                x = x + a Wo
                h = rmsnorm(x) * ln2
                x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * final_norm) W_unembed   (embed^T when tied)

Everything is float32 with matmuls at ``highest`` precision; the weights
are the bf16 values the served model holds, which are exact in float32.
It runs one sequence at a time, layer by layer under ``lax.scan``, with
attention in blocks of query rows, so an 8k-token sequence at Mistral-7B
widths fits beside the weights on one 16 GB chip.

``control=True`` is the same forward one precision step lower: every
matmul input (weights per output column, activations per row) rounded to
float8 e4m3 with a scale, accumulation in float32.  It is what the
benchmark's check has to reject.

Weight names are the paths of the served program's parameter tree
(``groups/0/...`` holds the layers stacked on a leading axis); nothing of
the program is imported here.  Beside the forward the module states what
the benchmark needs to know of this architecture: the program's
configuration for a configuration file (``program_config``, the one place
that names the program's config class), the weights and their laws
(``weight_specs``), and what a step costs (``step_terms``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 256
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def weight_specs(hf: dict):
    """[(name, shape, kind)] of every weight; ``kind`` picks the law the
    generator draws it from (see chipbench.weights)."""
    d = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    nkv = hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    ff = hf["intermediate_size"]
    v = hf["vocab_size"]
    L = hf["num_hidden_layers"]
    g = "groups/0/"
    specs = [
        ("embed", (v, d), "embed"),
        ("final_norm", (d,), "norm"),
        (g + "ln1", (L, d), "norm"),
        (g + "ln2", (L, d), "norm"),
        (g + "mixer/wq", (L, d, nh * hd), "dense"),
        (g + "mixer/wk", (L, d, nkv * hd), "dense"),
        (g + "mixer/wv", (L, d, nkv * hd), "dense"),
        (g + "mixer/wo", (L, nh * hd, d), "dense"),
        (g + "ffn/w_gate", (L, d, ff), "dense"),
        (g + "ffn/w_up", (L, d, ff), "dense"),
        (g + "ffn/w_down", (L, ff, d), "dense"),
    ]
    if qkv_bias(hf):
        specs += [(g + "mixer/bq", (L, nh * hd), "bias"),
                  (g + "mixer/bk", (L, nkv * hd), "bias"),
                  (g + "mixer/bv", (L, nkv * hd), "bias")]
    if not hf["tie_word_embeddings"]:
        specs.append(("unembed", (d, v), "dense"))
    return specs


def program_config(hf: dict):
    """The program's ModelConfig for a configuration file, checked against
    every published key that shapes what the model computes."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(**hf["program"])
    d = hf["hidden_size"]
    qwen2 = hf["model_type"] == "qwen2"
    want = {
        "family": "dense",
        "d_model": d, "n_layers": hf["num_hidden_layers"],
        "n_heads": hf["num_attention_heads"],
        "n_kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim") or d // hf["num_attention_heads"],
        "d_ff": hf["intermediate_size"],
        "vocab_size": hf["vocab_size"],
        "rope_theta": hf["rope_theta"],
        "norm_eps": hf["rms_norm_eps"],
        "tie_embeddings": hf["tie_word_embeddings"],
        "act": hf["hidden_act"],
        # Qwen2 has a bias on Q, K and V (and none on the output)
        "qkv_bias": qwen2,
        "sliding_window": None,
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    # what the program cannot compute at all
    for key, ok in (("attention_bias", False), ("mlp_bias", False),
                    ("rope_scaling", None)):
        if hf.get(key, ok) != ok:
            bad[key] = ("not served by the program", hf[key])
    if hf.get("sliding_window") is not None \
            and hf.get("use_sliding_window", True):
        bad["sliding_window"] = ("not served by the program",
                                 hf["sliding_window"])
    if hf["model_type"] not in ("llama", "mistral", "qwen2"):
        bad["model_type"] = ("llama, mistral or qwen2", hf["model_type"])
    if bad:
        raise ValueError(f"{hf['name']}: program config disagrees with "
                         f"the published keys (program, file): {bad}")
    return cfg


def step_terms(hf: dict) -> dict:
    """``chipbench.counts.Terms`` of this model served in bf16: every
    layer's projections and gated FFN per token, 4 * head_dim operations
    per query head and (query, key) pair, the unembedding of one sampled
    row, every weight read once (embedding rows gathered for the step's
    tokens left out, a few KB), and K and V of one position in every
    layer."""
    d = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    nkv = hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    ff = hf["intermediate_size"]
    L = hf["num_hidden_layers"]
    v = hf["vocab_size"]
    proj = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff
    per_layer = proj + 2 * d
    if qkv_bias(hf):
        per_layer += nh * hd + 2 * nkv * hd
    return {"linear_flops_per_token": 2 * proj * L,
            "attn_flops_per_pair": 4 * nh * hd * L,
            "unembed_flops": 2 * d * v,
            "weight_bytes": 2 * (per_layer * L + d + d * v),
            "state_bytes_per_token": 2 * 2 * nkv * hd * L,
            "state_bytes_per_request": 0}


def qkv_bias(hf: dict) -> bool:
    return (hf.get("model_type") == "qwen2"
            or bool(hf.get("attention_bias", False)))


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(x, w, control):
    """x [T, k] @ w [k, n] in float32 (or through float8 for the control)."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, n, hd]; rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    half = hd // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA over one sequence.  q [T, nh, hd], k/v [T, nkv, hd]."""
    T, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    qb = QUERY_BLOCK
    assert T % qb == 0, (T, qb)
    kpos = jnp.arange(T)

    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        qi = qi.reshape(qb, nkv, g, hd)
        s = jnp.einsum("qkgh,skh->kgqs", qi, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skh->qkgh", p, v, precision=HIGHEST)
        return o.reshape(qb, nh * hd)

    return lax.map(block, jnp.arange(T // qb)).reshape(T, nh * hd)


def logits_at(w: dict, hf: dict, tokens, read_pos, *, control=False):
    """Logits [P, V] at positions ``read_pos`` [P] of the sequence
    ``tokens`` [T] (T a multiple of QUERY_BLOCK; padding after the real
    tokens does not reach earlier positions under the causal mask)."""
    d = hf["hidden_size"]
    nh = hf["num_attention_heads"]
    nkv = hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // nh
    eps = hf["rms_norm_eps"]
    theta = hf["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    g = "groups/0/"
    layers = {k[len(g):]: a for k, a in w.items() if k.startswith(g)}
    bias = qkv_bias(hf)

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = _mm(h, lw["mixer/wq"], control)
        k = _mm(h, lw["mixer/wk"], control)
        v = _mm(h, lw["mixer/wv"], control)
        if bias:
            q = q + lw["mixer/bq"].astype(jnp.float32)
            k = k + lw["mixer/bk"].astype(jnp.float32)
            v = v + lw["mixer/bv"].astype(jnp.float32)
        q = _rope(q.reshape(T, nh, hd), pos, theta)
        k = _rope(k.reshape(T, nkv, hd), pos, theta)
        v = v.reshape(T, nkv, hd)
        x = x + _mm(_attention(q, k, v), lw["mixer/wo"], control)
        h = _rms(x, lw["ln2"], eps)
        f = jax.nn.silu(_mm(h, lw["ffn/w_gate"], control)) \
            * _mm(h, lw["ffn/w_up"], control)
        return x + _mm(f, lw["ffn/w_down"], control), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = lax.scan(layer, x, layers)
    x = _rms(x[read_pos], w["final_norm"], eps)
    head = w["embed"].T if hf["tie_word_embeddings"] else w["unembed"]
    return _mm(x, head, control)
