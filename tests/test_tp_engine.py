"""Tensor-parallel engines: the tp x pp grid against the single-device
reference.

Equivalence contract (README §Tensor-parallel x pipeline-parallel):

* ``tp=1`` (any pp) is BIT-identical to the single-device engine — the
  unsharded code path is untouched (``engine.tp_mesh is None``) and the
  pipeline partition slices the layer scan without altering it;
* ``tp>1`` is equivalent to a TOLERANCE tier: TP all-reduces legitimately
  reorder float accumulation, so per-step logits agree within
  ``_ATOL``/``_RTOL`` (pinned directly at the stack level below) while
  token streams may in principle diverge at an exact argmax/sampling tie.
  Token-level tests therefore assert a prefix-agreement fraction rather
  than equality; on CPU's deterministic reductions the seeds below agree
  exactly, and the thresholds only leave room for tie flips.

All tp>1 / pp>1 cases need forced host devices; ``tests/conftest.py``
forces 8 before the first jax import, so they run under plain ``pytest``.
The ``_need`` guards only fire when an explicit ``XLA_FLAGS`` export
deliberately pins a smaller device count.
"""
import dataclasses
import itertools

import jax
import numpy as np
import pytest

import repro.scheduler.request as request_mod
from repro import env
from repro import sharding as shd
from repro.configs import get_config
from repro.core import ChunkWork, DecodeWork, IterationPlan, SamplingParams
from repro.core.engine import Engine
from repro.models import build_model
from repro.scheduler import Request
from repro.serving import Server

# tolerance tier for tp>1 logits (fp32 on CPU; TP all-reduce reordering
# perturbs at ~1e-7 for these widths — an order of magnitude of headroom)
_ATOL = 2e-5
_RTOL = 2e-5

_CFG = dataclasses.replace(
    get_config("tinyllama-1.1b").reduced(), n_layers=4, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)
_PARAMS = None

_PAGED_PALLAS = env.get("REPRO_PAGED_ATTN_BACKEND") == "pallas"


def _cfg_params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = build_model(_CFG).init_params(jax.random.PRNGKey(0))
    return _CFG, _PARAMS


def _reqs(n=5, seed=0):
    request_mod._ids = itertools.count()     # deterministic req ids
    rng = np.random.default_rng(seed)
    return [Request(prompt=[int(t) for t in
                            rng.integers(0, _CFG.vocab_size,
                                         int(rng.integers(6, 21)))],
                    max_new_tokens=int(rng.integers(3, 7)))
            for _ in range(n)]


def _need(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs >= {n} devices (conftest forces 8 unless an "
               f"explicit XLA_FLAGS export pins fewer)")


def _prefix_agreement(ref: dict, got: dict):
    """-> (mean per-request longest-common-prefix fraction, fraction of
    requests with fully identical streams)."""
    assert ref.keys() == got.keys()
    fracs, exact = [], 0
    for rid in ref:
        a, b = ref[rid], got[rid]
        assert len(a) == len(b)
        lcp = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                   len(a))
        fracs.append(lcp / len(a) if a else 1.0)
        exact += a == b
    return sum(fracs) / len(fracs), exact / len(ref)


def _serve(pp, tp, paged, temperature=0.0, seed=7):
    cfg, params = _cfg_params()
    srv = Server(cfg, params, policy="sarathi", chunk_size=8, n_slots=4,
                 max_len=64, pp=pp, tp=tp, paged=paged, block_size=8,
                 seed=seed, sampling=SamplingParams(temperature=temperature))
    return srv.run(_reqs()).outputs


# ---------------------------------------------------------------- tp == 1
def test_tp1_is_the_unsharded_path():
    """The bit-identity pin: tp=1 must not place, shard, or mesh anything
    — it is literally the pre-TP engine."""
    cfg, params = _cfg_params()
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=1, tp=1)
    assert eng.tp == 1 and eng.tp_mesh is None
    leaf = jax.tree.leaves(eng.cache)[0]
    assert len(leaf.devices()) == 1


@pytest.mark.parametrize("paged", [False, True])
def test_tp1_outputs_bit_identical_to_default(paged):
    """Server(tp=1) == Server() exactly, dense and paged."""
    assert _serve(1, 1, paged) == _serve_default(paged)


def _serve_default(paged):
    cfg, params = _cfg_params()
    srv = Server(cfg, params, policy="sarathi", chunk_size=8, n_slots=4,
                 max_len=64, paged=paged, block_size=8, seed=7)
    return srv.run(_reqs()).outputs


# ------------------------------------------------------- shared policy
def test_engines_and_launch_share_one_policy():
    """No duplicated leaf rules: the launch import path and the serving
    placement layer must resolve to the SAME policy functions."""
    from repro.launch import shardings as launch_sh
    from repro.sharding import policy
    assert launch_sh.param_pspecs is policy.param_pspecs
    assert launch_sh.cache_pspecs is policy.cache_pspecs
    assert launch_sh.use_fsdp is policy.use_fsdp


def test_paged_pool_leaves_have_tp_specs():
    """Satellite: the fused pkv pool leaf [n_blocks, nk, 2, bs, hd] must
    shard under TP (kv-head dim here: nk=2 divides tp=2), not
    replicate."""
    cfg, _ = _cfg_params()
    model = build_model(cfg)
    shapes = jax.eval_shape(
        lambda: model.init_cache(3, 64, jax.numpy.float32,
                                 paged_blocks=17, block_size=8))
    mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    specs = shd.cache_pspecs(cfg, shapes, rows_axes=None, mesh=mesh)

    found = []

    def check(path, spec):
        keys = [getattr(p, "key", None) for p in path]
        if keys and keys[-1] == "pkv":
            found.append(spec)

    jax.tree_util.tree_map_with_path(check, specs)
    assert found, "no pool leaves in the paged cache spec tree"
    for spec in found:
        assert "model" in tuple(spec), f"pool leaf replicated: {spec}"
        # the kv-head axis is the sharded one: each head's (K, V) pair
        # stays on one shard
        assert tuple(spec)[-4] == "model"


def test_mesh_derived_axis_sizes():
    """Satellite: axis sizes come from the mesh, not hard-coded 16s.
    tinyllama's d_ff/head dims divide 16 AND 2, but its vocab (32000)
    divides 16 only — a (1, 3)-mesh policy must replicate what 3 doesn't
    divide, and a mesh without a data axis must never emit DATA specs."""
    cfg = get_config("tinyllama-1.1b")
    shapes = jax.eval_shape(
        lambda: build_model(cfg).init_params(jax.random.PRNGKey(0)))
    m3 = jax.sharding.AbstractMesh((1, 3), ("data", "model"))
    specs = shd.param_pspecs(cfg, shapes, mesh=m3)
    # 32000 % 3 != 0 -> embed replicates on the 3-mesh, shards on 16
    assert specs["embed"] == jax.sharding.PartitionSpec(None, None)
    specs16 = shd.param_pspecs(cfg, shapes)          # default production 16
    assert specs16["embed"] == jax.sharding.PartitionSpec("model", None)
    with pytest.raises(ValueError):
        shd.param_pspecs(cfg, shapes, mesh=m3, model_axis=4)


# ------------------------------------------------------ tolerance tier
@_need(2)
@pytest.mark.parametrize("paged", [False, True])
def test_tp2_logits_within_tolerance(paged):
    """The tp>1 equivalence contract, pinned at its source: the same
    packed step over sharded vs unsharded params/cache produces logits
    within the documented tolerance (all-reduce reordering only).  Runs
    under BOTH paged backends: with pallas the kernels go through the
    shard_map-over-kv-heads wrapper (the mesh hint an engine would set)."""
    cfg, params = _cfg_params()
    model = build_model(cfg)
    kw = dict(paged_blocks=17, block_size=8) if paged else {}
    cache = model.init_cache(3, 64, jax.numpy.float32, **kw)
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=2, paged=paged, block_size=8)
    eng.add_request(0)
    eng.add_request(1)
    pk = eng._pack(ChunkWork(0, [1, 2, 3, 4, 5], 0, True),
                   [DecodeWork(1, 9, 3)])

    def fwd(p, c):
        cl, dl, _, _ = model.forward_packed(p, pk, c)
        return cl, dl

    ref_cl, ref_dl = jax.jit(fwd)(params, cache)
    mesh = shd.make_tp_mesh(2)
    sp = shd.shard_params(cfg, params, mesh)
    sc = shd.shard_cache(cfg, cache, mesh)
    from repro.models import blocks as bk
    bk.set_paged_attn_mesh(mesh if (paged and _PAGED_PALLAS) else None)
    try:
        tp_cl, tp_dl = jax.jit(fwd)(sp, sc)
    finally:
        bk.set_paged_attn_mesh(None)
    np.testing.assert_allclose(np.asarray(ref_cl), np.asarray(tp_cl),
                               atol=_ATOL, rtol=_RTOL)
    np.testing.assert_allclose(np.asarray(ref_dl), np.asarray(tp_dl),
                               atol=_ATOL, rtol=_RTOL)


@_need(2)
def test_tp2_params_and_cache_actually_shard():
    cfg, params = _cfg_params()
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=1, tp=2)
    w = eng.params["groups"][0]["ffn"]["w_gate"]
    assert len(w.devices()) == 2
    assert "model" in tuple(w.sharding.spec)
    k = jax.tree.leaves(eng.cache)[0]
    assert len(k.devices()) == 2


@_need(2)
def test_tp2_paged_pallas_backend_accepted(monkeypatch):
    """The PR-4 restriction is LIFTED: tp=2 + pallas (nk=2 divides 2)
    builds, serves, and matches the unsharded pallas engine.  Greedy on
    CPU's deterministic reductions: these seeds agree token-for-token
    (the contract itself is the 2e-5 logits tier pinned above)."""
    monkeypatch.setenv("REPRO_PAGED_ATTN_BACKEND", "pallas")
    cfg, params = _cfg_params()
    prompt = [1, 5, 9, 13, 2, 7]

    def gen(tp):
        eng = Engine(cfg, params, n_slots=1, max_len=64, chunk_size=8,
                     decode_slots=1, paged=True, block_size=8, tp=tp)
        eng.add_request(0)
        out = [eng.execute(IterationPlan(chunk=ChunkWork(
            0, prompt, 0, True)))[0]]
        for _ in range(2):
            out.append(eng.execute(IterationPlan(decodes=[DecodeWork(
                0, out[-1], len(prompt) + len(out) - 1)]))[0])
        eng.release(0)
        return out

    want = gen(1)
    got = gen(2)                                # previously: raised
    assert got == want


def test_tp_paged_pallas_needs_divisible_kv_heads(monkeypatch):
    """Residual restriction: shard_map keeps whole kv heads per
    shard, so nk % tp != 0 (here 2 % 3) is still rejected up front."""
    monkeypatch.setenv("REPRO_PAGED_ATTN_BACKEND", "pallas")
    cfg, params = _cfg_params()
    with pytest.raises(NotImplementedError, match="divisible"):
        Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
               decode_slots=1, paged=True, block_size=8, tp=3)


# ------------------------------------------------------- tp x pp grid
@_need(8)
@pytest.mark.parametrize("pp", [1, 2])
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("paged", [False, True])
def test_grid_tokens_match_reference(pp, tp, paged):
    """tp x pp x {dense,paged}, greedy: tp=1 rows must be bit-identical;
    tp=2 rows must meet the tolerance-tier token contract.  Under
    REPRO_PAGED_ATTN_BACKEND=pallas the paged tp=2 rows exercise the
    shard_map'd fused-pool kernels (the previously rejected case)."""
    ref = _serve_default(paged)
    got = _serve(pp, tp, paged)
    if tp == 1:
        assert got == ref                     # bit-identity pinned
    else:
        mean_frac, exact = _prefix_agreement(ref, got)
        assert mean_frac >= 0.75 and exact >= 0.6, \
            f"tp={tp} pp={pp} diverged beyond tolerance: " \
            f"prefix={mean_frac:.2f} exact={exact:.2f}"


@_need(8)
@pytest.mark.parametrize("pp", [1, 2])
def test_grid_stochastic_sampling(pp):
    """temperature > 0 under TP: the PRNG chain is sharding-independent,
    so sampled streams meet the same tolerance contract."""
    ref = _serve(1, 1, False, temperature=1.0)
    got = _serve(pp, 2, False, temperature=1.0)
    mean_frac, exact = _prefix_agreement(ref, got)
    assert mean_frac >= 0.75 and exact >= 0.6


@_need(8)
def test_pp2_tp2_stage_shards_live_on_stage_rows():
    """Acceptance: PipelineEngine(tp=2, pp=2) places each stage's shards
    on ITS row of the (pp, tp) device grid — 4 distinct devices."""
    from repro.core import PipelineEngine
    cfg, params = _cfg_params()
    eng = PipelineEngine(cfg, params, pp=2, tp=2, n_slots=2, max_len=64,
                         chunk_size=8, decode_slots=1)
    rows = []
    for s in range(2):
        devs = set()
        for leaf in jax.tree.leaves(eng.stage_params[s]):
            devs |= set(leaf.devices())
        assert len(devs) == 2, f"stage {s} not sharded over 2 chips"
        rows.append(devs)
    assert not (rows[0] & rows[1]), "stages share devices"
    w = eng.stage_params[0]["groups"][0]["ffn"]["w_gate"]
    assert "model" in tuple(w.sharding.spec)


@_need(2)
def test_tp1_honours_explicit_device():
    """devices= is placement-only at tp=1 but must not be dropped."""
    cfg, params = _cfg_params()
    dev = jax.devices()[1]
    eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=8,
                 decode_slots=1, tp=1, devices=[dev])
    assert {next(iter(leaf.devices()))
            for leaf in jax.tree.leaves(eng.cache)} == {dev}


@_need(2)
def test_tp2_single_stage_summary_reports_tp():
    """pp=1 tp=2 runs through the serial online loop; the summary must
    still carry the engine's TP degree."""
    from repro.serving import OnlineServer, format_table, online_workload
    cfg, params = _cfg_params()
    request_mod._ids = itertools.count()
    reqs = online_workload(3, rate=32.0, pd_ratio=4.0, min_len=6,
                           max_len=16, vocab_size=cfg.vocab_size, seed=5)
    srv = OnlineServer(cfg, params, policy="sarathi_serve", chunk_size=8,
                       n_slots=4, max_len=64, tp=2)
    res = srv.run(reqs)
    s = res.summary()
    assert s.tp == 2 and s.pp == 1
    assert "tp=2" in format_table(s)


@_need(8)
def test_pp2_tp2_online_pipelined_serves_to_completion():
    from repro.serving import OnlineServer, online_workload
    cfg, params = _cfg_params()
    request_mod._ids = itertools.count()
    reqs = online_workload(6, rate=32.0, pd_ratio=4.0, min_len=6,
                           max_len=20, vocab_size=cfg.vocab_size, seed=6)
    srv = OnlineServer(cfg, params, policy="sarathi_serve", chunk_size=8,
                       n_slots=4, max_len=64, pp=2, tp=2,
                       policy_kwargs={"max_chunks_per_iter": 1})
    res = srv.run(reqs)
    for r in reqs:
        assert len(res.outputs[r.req_id]) == r.max_new_tokens
    s = res.summary()
    assert s.pp == 2 and s.tp == 2
    assert 0.0 <= s.bubble_fraction < 1.0
