"""Granite-8B-Code's biases (``attention_bias``: Q, K, V and O;
``mlp_bias``: gate, up and down) through the serving path, against the
chip benchmark's plain float32 reference (``references/granite_dense.py``).

A tiny Granite-shaped model (every width cut, every bias kept) is served
in float32 by the paged engine: multi-chunk prefill, then decodes riding
beside another request's chunks.  Its logits are compared with the
reference's on the same weights, every bias drawn nonzero (std 0.1, as
the benchmark's ``bias`` law), so a bias the program drops or adds twice
moves them by about the bias's size.

Tolerances: both sides are float32 and differ only in the order of
summation (blocked online softmax, packed rows, another matmul tiling),
which moves logits of size O(1) by about 1e-6; ``_TOL`` = 1e-4 leaves two
orders of room, while each planted fault below moves them by 1e-2 or
more.  The tp=2 tier is the repository's documented 2e-5
(``tests/test_tp_engine.py``).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from repro import sharding as shd
from repro.configs import get_config
from repro.core import ChunkWork, DecodeWork, IterationPlan
from repro.core.engine import Engine
from repro.models import blocks, build_model
from repro.models import common as cm

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from chipbench import program, spec, weights  # noqa: E402

REF = spec.load_module(BENCH / "references" / "granite_dense.py")

_TOL = 1e-4
_TP_TOL = 2e-5
BIASES = {"bq": "qkv", "bk": "qkv", "bv": "qkv", "bo": "o_proj",
          "b_gate": "ffn", "b_up": "ffn", "b_down": "ffn"}

CFG = dataclasses.replace(
    get_config("granite-8b"), n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256, max_seq_len=128)
HF = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
      "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
      "vocab_size": 256, "rope_theta": CFG.rope_theta, "rms_norm_eps": 1e-5,
      "tie_word_embeddings": CFG.tie_embeddings, "attention_bias": True,
      "mlp_bias": True}


def _leaf(path) -> str:
    return weights.path_name(path).rsplit("/", 1)[-1]


def _params():
    """The program's float32 parameters with every bias drawn nonzero."""
    params = build_model(CFG).init_params(jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        0.1 * jax.random.normal(k, a.shape, a.dtype)
        if _leaf(p) in BIASES else a
        for k, (p, a) in zip(keys, leaves)])


def _reference_logits(params, tokens):
    """The reference's logits at every position of ``tokens``."""
    flat = {weights.path_name(p): a for p, a in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    T = len(tokens)
    toks = np.zeros((-(-T // REF.QUERY_BLOCK) * REF.QUERY_BLOCK,), np.int32)
    toks[:T] = tokens
    return np.asarray(REF.logits_at(flat, HF, jnp.asarray(toks),
                                    jnp.arange(T)))


def _serve(params, prompts, n_new, chunk=8):
    """Serve ``prompts`` on the paged engine: request 0's prompt chunk by
    chunk alone, then request 1's chunks each beside request 0's decode,
    then both decoding.  Returns ({rid: served tokens}, {rid: the chunk
    logits of its last prompt chunk})."""
    eng = Engine(CFG, params, n_slots=2, max_len=64, chunk_size=chunk,
                 decode_slots=2, paged=True, block_size=8)
    out = {0: [], 1: []}
    last = {}

    def decode(rid):
        p = prompts[rid]
        return DecodeWork(rid, out[rid][-1], len(p) + len(out[rid]) - 1)

    for rid in (0, 1):
        eng.add_request(rid)
        p = prompts[rid]
        for s in range(0, len(p), chunk):
            c = ChunkWork(rid, p[s:s + chunk], s, s + chunk >= len(p))
            dec = [decode(0)] if rid == 1 else []
            r = eng.execute(IterationPlan(chunk=c, decodes=dec))
            for k, t in r.items():
                out[k].append(t)
            if c.is_last:
                last[rid] = np.asarray(eng.chunk_logits[0])
    while min(len(v) for v in out.values()) < n_new:
        r = eng.execute(IterationPlan(decodes=[
            decode(k) for k in (0, 1) if len(out[k]) < n_new]))
        for k, t in r.items():
            out[k].append(t)
    return out, last


PROMPTS = [np.random.default_rng(3).integers(0, 256, 21).tolist(),
           np.random.default_rng(4).integers(0, 256, 13).tolist()]


def _errors():
    """(widest chunk-logit error, widest gap between the reference's best
    logit and the served token's) of a served run against the reference,
    on the paged backend ``REPRO_PAGED_ATTN_BACKEND`` names."""
    params = _params()
    out, last = _serve(params, PROMPTS, 6)
    err, gap = 0.0, 0.0
    for rid, p in enumerate(PROMPTS):
        seq = p + out[rid][:-1]
        lg = _reference_logits(params, seq)[len(p) - 1:]
        err = max(err, float(np.abs(last[rid] - lg[0]).max()))
        got = lg[np.arange(len(out[rid])), out[rid]]
        gap = max(gap, float((lg.max(-1) - got).max()))
    return err, gap


@pytest.fixture(params=["xla", "pallas"])
def backend(request, monkeypatch):
    monkeypatch.setenv("REPRO_PAGED_ATTN_BACKEND", request.param)
    return request.param


def test_served_granite_matches_the_float32_reference(backend):
    err, gap = _errors()
    assert err <= _TOL and gap <= _TOL, (err, gap)


def _drop(fn, name):
    def dropped(p, *a, **kw):
        return fn({k: v for k, v in p.items() if k != name}, *a, **kw)
    return dropped


FAULTS = {"bo_dropped": (blocks, "out_proj", "bo"),
          "b_down_dropped": (cm, "glu_ffn", "b_down")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_dropped_bias_fails_the_comparison(fault, monkeypatch):
    mod, fn, name = FAULTS[fault]
    monkeypatch.setattr(mod, fn, _drop(getattr(mod, fn), name))
    err, gap = _errors()
    assert err > 100 * _TOL, (fault, err, gap)


# ------------------------------------------------------------- tp = 2
def _per_shard_out_proj(mesh):
    """The fault: each shard adds ``bo`` to its partial product before the
    reduction, so the sum holds tp copies of it."""
    P = jax.sharding.PartitionSpec

    def proj(p, out):
        def part(o, w, b):
            return jax.lax.psum(o @ w + b, "model")
        return jax.shard_map(part, mesh=mesh,
                             in_specs=(P(None, "model"), P("model", None),
                                       P()),
                             out_specs=P(), check_vma=False)(
            out, p["wo"], p["bo"])
    return proj


def _tp_logits(backend, per_shard=False, monkeypatch=None):
    """Logits of one hybrid packed step (a chunk beside a decode), at tp=1
    and at tp=2 on the same params and cache."""
    params = _params()
    model = build_model(CFG)
    eng = Engine(CFG, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=2, paged=True, block_size=8)
    eng.add_request(0)
    eng.add_request(1)
    eng.execute(IterationPlan(chunk=ChunkWork(1, PROMPTS[1][:8], 0, False)))
    pk = eng._pack(ChunkWork(0, PROMPTS[0][:8], 0, False),
                   [DecodeWork(1, 7, 8)])
    cache = eng.cache

    def fwd(p, c):
        cl, dl, _, _ = model.forward_packed(p, pk, c)
        return cl, dl

    want = jax.jit(fwd)(params, cache)
    mesh = shd.make_tp_mesh(2)
    if per_shard:
        monkeypatch.setattr(blocks, "out_proj", _per_shard_out_proj(mesh))
    blocks.set_paged_attn_mesh(mesh if backend == "pallas" else None)
    try:
        got = jax.jit(fwd)(shd.shard_params(CFG, params, mesh),
                           shd.shard_cache(CFG, cache, mesh))
    finally:
        blocks.set_paged_attn_mesh(None)
    return [np.asarray(x) for x in want], [np.asarray(x) for x in got]


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 host devices")
def test_tp2_equals_tp1_with_biases(backend):
    want, got = _tp_logits(backend)
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, atol=_TP_TOL, rtol=_TP_TOL)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 host devices")
def test_a_bias_added_per_shard_fails_tp2(monkeypatch):
    want, got = _tp_logits("xla", per_shard=True, monkeypatch=monkeypatch)
    assert max(float(np.abs(g - w).max()) for w, g in zip(want, got)) \
        > 100 * _TP_TOL


def test_output_side_biases_are_replicated_under_tp():
    shapes = jax.eval_shape(build_model(CFG).init_params,
                            jax.random.PRNGKey(0))
    specs = shd.param_pspecs(CFG, shapes, model_axis=2, data_axis=1)
    layer = specs["groups"][0]
    P = jax.sharding.PartitionSpec
    assert layer["mixer"]["bo"] == P(None, None)
    assert layer["ffn"]["b_down"] == P(None, None)
    assert layer["ffn"]["b_gate"] == layer["ffn"]["b_up"] \
        == P(None, "model")
    assert layer["mixer"]["bq"] == P(None, "model")


@pytest.mark.parametrize("change", [
    {"act": "gelu"},
    {"family": "moe", "n_experts": 4, "top_k": 2},
    {"family": "hybrid", "block_pattern": ("rglru", "local_attn")},
])
def test_mlp_bias_is_refused_where_the_ffn_would_build_none(change):
    """Only the gated silu FFN of dense-style layers holds ``b_gate``,
    ``b_up`` and ``b_down``; elsewhere the key would be silently
    ignored, so the config refuses it."""
    with pytest.raises(ValueError, match="mlp_bias"):
        dataclasses.replace(CFG, **change)
    dataclasses.replace(CFG, **change, mlp_bias=False)


# ------------------------------------------ bias-free configurations
def _parent_out_proj(p, out):
    return out @ p["wo"]


def _parent_glu_ffn(p, x, act="silu"):
    a = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    h = a(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def _step_jaxpr(cfg):
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=2, paged=True, block_size=8)
    pk = eng._pack(None, [], pad_chunk=True)
    return params, str(jax.make_jaxpr(eng._step_impl)(
        params, pk, eng.cache, eng._key))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-0.5b"])
def test_bias_free_configs_build_and_trace_as_before(arch, monkeypatch):
    """A configuration without the new keys has no new leaf, and its
    packed step traces to the jaxpr of the code before the biases."""
    cfg = get_config(arch).reduced()
    assert not (cfg.o_bias or cfg.mlp_bias)
    params, now = _step_jaxpr(cfg)
    names = {_leaf(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert not names & {"bo", "b_gate", "b_up", "b_down"}
    assert ("bq" in names) == cfg.qkv_bias
    monkeypatch.setattr(blocks, "out_proj", _parent_out_proj)
    monkeypatch.setattr(cm, "glu_ffn", _parent_glu_ffn)
    _, before = _step_jaxpr(cfg)
    assert now == before


# ------------------------------------------------------- named scopes
_SHAPE_OPS = {"broadcast_in_dim", "reshape", "convert_element_type",
              "squeeze", "expand_dims"}


def _bias_adds(jaxpr, tainted, stack=()):
    """[(bias leaf, name stack)] of every ``add`` with an operand that is
    a bias (``tainted``: var -> leaf name), through shape-only ops and
    into nested jaxprs (the layer scan, jitted calls)."""
    found = []
    tainted = dict(tainted)
    for e in jaxpr.eqns:
        names = stack + tuple(s.name for s in e.source_info.name_stack.stack)
        hit = [tainted[v] for v in e.invars
               if not isinstance(v, Literal) and v in tainted]
        if e.primitive.name == "add" and hit:
            found += [(h, "/".join(names)) for h in hit]
        elif e.primitive.name in _SHAPE_OPS and hit:
            tainted[e.outvars[0]] = hit[0]
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns") and len(sub.invars) == len(e.invars):
                inner = {sv: tainted[ov] for sv, ov in
                         zip(sub.invars, e.invars)
                         if not isinstance(ov, Literal)
                         and ov in tainted}
                found += _bias_adds(sub, inner, names)
    return found


def test_every_bias_add_lies_under_its_projection_scope():
    """On the packed step's jaxpr, each bias leaf reaches an ``add``, and
    the scope path the benchmark's readers give that add
    (``program.scope_path`` over the step's scopes) holds the scope of
    the bias's projection."""
    params = _params()
    eng = Engine(CFG, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=2, paged=True, block_size=8)
    pk = eng._pack(None, [], pad_chunk=True)
    closed = jax.make_jaxpr(eng._step_impl)(params, pk, eng.cache, eng._key)
    scopes = program.named_scopes(closed.jaxpr)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    tainted = {v: _leaf(p) for v, (p, _) in
               zip(closed.jaxpr.invars, leaves) if _leaf(p) in BIASES}
    assert sorted(set(tainted.values())) == sorted(BIASES)
    adds = _bias_adds(closed.jaxpr, tainted)
    assert {b for b, _ in adds} == set(BIASES)
    for b, names in adds:
        path = program.scope_path(names, scopes).split("/")
        assert BIASES[b] in path, (b, names)
