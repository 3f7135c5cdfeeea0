"""Phase-disaggregated serving: KV handoff identity + routing + accounting.

The tentpole contract: a prefill->decode KV handoff is a PURE cache
relocation, so greedy token outputs of a disaggregated ReplicaSet are
bit-identical to the monolithic engine — across dense/paged layouts,
tp in {1, 2} replicas, and unequal pp between the phases.  The paged
property test pins the mechanics: block tables are REMAPPED (contents
move, ids don't), pool accounting is conserved across the two pools, and
the reserved scratch block 0 is never transferred.
"""
import dataclasses
import itertools

import jax
import numpy as np
import pytest
from _prop import given, settings, strategies as st

import repro.scheduler.request as request_mod
from repro import env
from repro.cache import BlockManager
from repro.configs import get_config
from repro.core.engine import (Engine, _extract_state, _install_state)
from repro.models import build_model
from repro.scheduler import DisaggRouter, Request
from repro.serving import OnlineServer, ReplicaSet
from repro.sim.cost_model import kv_handoff_bytes, kv_transfer_time
from repro.sim.hardware import A100

_CFG = dataclasses.replace(
    get_config("tinyllama-1.1b").reduced(), n_layers=2, d_model=32,
    n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)
_PARAMS = None


def _cfg_params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = build_model(_CFG).init_params(jax.random.PRNGKey(0))
    return _CFG, _PARAMS


def _need(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs >= {n} devices (conftest forces 8 unless an "
               f"explicit XLA_FLAGS export pins fewer)")


def _reqs(n=5, seed=0):
    request_mod._ids = itertools.count()     # deterministic req ids
    rng = np.random.default_rng(seed)
    return [Request(prompt=[int(t) for t in
                            rng.integers(0, _CFG.vocab_size,
                                         int(rng.integers(6, 21)))],
                    max_new_tokens=int(rng.integers(1, 7)),
                    arrival_time=0.01 * i)
            for i in range(n)]


_KW = dict(chunk_size=8, n_slots=4, max_len=64, max_prompt_len=24,
           block_size=8, seed=7)


def _ref_outputs(paged, tp=1):
    cfg, params = _cfg_params()
    srv = OnlineServer(cfg, params, policy="sarathi_serve", paged=paged,
                       tp=tp, **_KW)
    return srv.run(_reqs()).outputs


def _disagg_outputs(paged, *, tp=1, chunked=True, n_prefill=1, n_decode=1,
                    pp=(1, 1), n_blocks=None):
    cfg, params = _cfg_params()
    if max(pp) > 1:
        cfg = dataclasses.replace(cfg, n_layers=4)   # >= 1 group per stage
        params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    rs = ReplicaSet(cfg, params, n_prefill=n_prefill, n_decode=n_decode,
                    prefill_chunked=chunked, paged=paged, prefill_tp=tp,
                    decode_tp=tp, prefill_pp=pp[0], decode_pp=pp[1],
                    n_blocks=n_blocks, hw=A100, **_KW)
    return rs.run(_reqs())


# ------------------------------------------------------- greedy identity
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("chunked", [True, False])
def test_disagg_bit_identical_to_monolithic(paged, chunked):
    """1 prefill + 1 decode replica, chunked (hybrid) and whole-prompt
    (DistServe) prefill: greedy outputs == the monolithic engine's."""
    res = _disagg_outputs(paged, chunked=chunked)
    assert res.outputs == _ref_outputs(paged)
    assert res.n_handoffs > 0                 # KV actually moved
    # event times stay causal across the handoff: an idle decode
    # replica's stale clock must never timestamp a token before the
    # request's prefill token (negative TBT) or its arrival
    for tr in res.traces.values():
        assert tr.token_times == sorted(tr.token_times)
        if tr.token_times:
            assert tr.ttft is not None and tr.ttft >= 0
        assert all(g >= 0 for g in tr.tbts)


@pytest.mark.parametrize("paged", [False, True])
def test_disagg_many_replicas_bit_identical(paged):
    """2 prefill + 2 decode replicas under the least-loaded router."""
    res = _disagg_outputs(paged, n_prefill=2, n_decode=2)
    assert res.outputs == _ref_outputs(paged)


@_need(2)
@pytest.mark.parametrize("paged", [False, True])
def test_disagg_tp2_bit_identical_to_tp2_monolithic(paged):
    """tp=2 replicas vs the tp=2 monolithic engine: BOTH sides run the
    same sharded compute, so disaggregation adds no divergence on top of
    the documented TP tolerance tier — outputs are bit-identical."""
    if paged and env.get("REPRO_PAGED_ATTN_BACKEND") == "pallas":
        pytest.skip("tp>1 rejects the paged pallas backend")
    res = _disagg_outputs(paged, tp=2)
    assert res.outputs == _ref_outputs(paged, tp=2)


@pytest.mark.parametrize("paged", [False, True])
def test_disagg_cross_pp_bit_identical(paged):
    """pp=2 prefill replica handing off to a pp=1 decode replica: stage
    slices reassemble into the canonical payload (each stage on its own
    host device; results are placement-independent)."""
    cfg, _ = _cfg_params()
    cfg4 = dataclasses.replace(cfg, n_layers=4)
    params4 = build_model(cfg4).init_params(jax.random.PRNGKey(0))
    srv = OnlineServer(cfg4, params4, policy="sarathi_serve", paged=paged,
                       **_KW)
    ref = srv.run(_reqs()).outputs
    res = _disagg_outputs(paged, pp=(2, 1))
    assert res.outputs == ref


def test_disagg_tight_pool_still_completes_exactly():
    """A small decode-side pool forces handoffs to queue (and possibly
    preemption-for-recompute); greedy outputs must stay exact."""
    res = _disagg_outputs(True, n_blocks=24)
    assert res.outputs == _ref_outputs(True)


# ----------------------------------------------------- handoff mechanics
def test_handoff_layout_mismatch_rejected():
    cfg, params = _cfg_params()
    dense = Engine(cfg, params, n_slots=2, max_len=32, chunk_size=8,
                   decode_slots=1)
    paged = Engine(cfg, params, n_slots=2, max_len=32, chunk_size=8,
                   decode_slots=1, paged=True, block_size=8)
    from repro.core.engine import ChunkWork, IterationPlan
    for eng in (dense, paged):
        eng.add_request(0)
        eng.execute(IterationPlan(chunk=ChunkWork(0, [1, 2, 3], 0, True)))
    h_dense = dense.extract_request(0)
    h_paged = paged.extract_request(0)
    assert h_dense.n_blocks == 0 and h_paged.n_blocks == 1
    paged.release(0)
    paged.add_request(1)
    with pytest.raises(ValueError, match="layout"):
        paged.install_request(1, h_dense)
    dense.release(0)
    dense.add_request(1)
    with pytest.raises(ValueError, match="layout"):
        dense.install_request(1, h_paged)
    # block-size mismatch across paged pools
    paged16 = Engine(cfg, params, n_slots=2, max_len=32, chunk_size=8,
                     decode_slots=1, paged=True, block_size=16)
    paged16.add_request(1)
    with pytest.raises(ValueError, match="block_size"):
        paged16.install_request(1, h_paged)


# ------------------------------------------ paged relocation (property)
# written with POSITIONAL strategies on purpose: the _prop shim must
# accept them exactly like real hypothesis (rightmost-parameter binding)
@settings(max_examples=12)
@given(st.integers(1, 6), st.integers(1, 40), st.integers(0, 3))
def test_paged_handoff_property(block_size, n_tokens, extra):
    """Block tables remap, pool accounting is conserved, scratch block 0
    never transfers — pinned on raw cache trees (no model, no jit)."""
    rng = np.random.default_rng(block_size * 1000 + n_tokens * 10 + extra)
    need = BlockManager(2, block_size).blocks_for_tokens(n_tokens)
    src_bm = BlockManager(1 + need + extra, block_size)
    dst_bm = BlockManager(1 + need + 2 * extra + 1, block_size)

    def pool(bm):
        return rng.standard_normal(
            (2, bm.n_blocks, bm.block_size, 2)).astype(np.float32)

    src = {"groups": {"pkv": pool(src_bm)},
           "tail": [{"k": rng.standard_normal((3, 4)).astype(np.float32)}]}
    dst = {"groups": {"pkv": pool(dst_bm)},
           "tail": [{"k": np.zeros((3, 4), np.float32)}]}
    dst_scratch_before = np.asarray(dst["groups"]["pkv"][:, 0]).copy()

    src_table = src_bm.ensure(7, n_tokens)
    assert 0 not in src_table                    # scratch never allocated
    assert src_bm.n_used == need

    state = jax.device_get(_extract_state(src, slot=1, table=src_table))
    # the payload is exactly the table's blocks, in table order
    np.testing.assert_array_equal(
        state["groups"]["pkv"], src["groups"]["pkv"][:, src_table])
    assert state["tail"][0]["k"].shape == (4,)   # slot row extracted

    dst_table = dst_bm.ensure(9, len(src_table) * block_size)
    assert 0 not in dst_table and len(dst_table) == len(src_table)
    out = jax.device_get(_install_state(dst, state, slot=2,
                                        table=dst_table))
    # contents moved to the REMAPPED destination blocks
    np.testing.assert_array_equal(
        np.asarray(out["groups"]["pkv"])[:, dst_table],
        src["groups"]["pkv"][:, src_table])
    np.testing.assert_array_equal(
        np.asarray(out["tail"][0]["k"])[2], state["tail"][0]["k"])
    # scratch block 0 untouched on the receiving pool
    np.testing.assert_array_equal(np.asarray(out["groups"]["pkv"])[:, 0],
                                  dst_scratch_before)
    # accounting conserved: src frees what dst now holds
    assert dst_bm.n_used == need
    assert src_bm.free(7) == need
    assert src_bm.n_used == 0


# --------------------------------------------------------------- router
class _Stub:
    def __init__(self, name, pload=0, dload=0, accept=True):
        self.name = name
        self._p, self._d, self._a = pload, dload, accept

    def prefill_load(self):
        return self._p

    def decode_load(self):
        return self._d

    def can_accept(self, req):
        return self._a


def test_router_least_loaded():
    r = DisaggRouter()
    a, b = _Stub("a", pload=10, dload=1), _Stub("b", pload=3, dload=5)
    assert r.pick_prefill([a, b]) is b
    assert r.pick_decode([a, b], None) is a
    b._a = False
    assert r.pick_decode([a, b], None) is a
    a._a = False
    assert r.pick_decode([a, b], None) is None   # all full -> queue


def test_router_round_robin_cycles():
    r = DisaggRouter("round_robin")
    a, b = _Stub("a"), _Stub("b")
    assert [r.pick_prefill([a, b]) for _ in range(4)] == [a, b, a, b]
    assert [r.pick_decode([a, b], None) for _ in range(3)] == [a, b, a]


def test_router_round_robin_stable_under_capacity_filtering():
    """Rotation walks replica IDENTITIES: a temporarily full replica is
    skipped without shifting which peers absorb the rest of the cycle
    (regression: the cursor used to index the capacity-FILTERED list, so
    who got a handoff depended on who happened to be full that instant)."""
    r = DisaggRouter("round_robin")
    a, b, c = _Stub("a"), _Stub("b"), _Stub("c")
    reps = [a, b, c]
    b._a = False
    # b full: the cycle covers the accepting replicas evenly, in order
    assert [r.pick_decode(reps, None) for _ in range(4)] == [a, c, a, c]
    # b recovers mid-rotation: it rejoins exactly at its place in the ring
    b._a = True
    assert [r.pick_decode(reps, None) for _ in range(3)] == [a, b, c]
    # everyone full -> None, and the cursor does not spin
    a._a = b._a = c._a = False
    assert r.pick_decode(reps, None) is None
    a._a = b._a = c._a = True
    assert r.pick_decode(reps, None) is a


def test_router_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown router policy"):
        DisaggRouter("hash")


# -------------------------------------------------- transfer cost model
def test_kv_transfer_time_term():
    assert kv_transfer_time(A100, 0) == 0.0
    t1 = kv_transfer_time(A100, 1e6)
    t2 = kv_transfer_time(A100, 2e6)
    assert 0 < t1 < t2
    # 2x the bytes is 2x the stream time (minus the fixed launch cost)
    assert (t2 - A100.kernel_overhead) == pytest.approx(
        2 * (t1 - A100.kernel_overhead))
    cfg = get_config("tinyllama-1.1b")
    assert kv_handoff_bytes(cfg, 100) == 100 * cfg.kv_bytes_per_token(2)
    assert kv_handoff_bytes(cfg, 0) == 0.0


def test_disagg_charges_transfer_on_the_clock():
    """Cost-model replicas: the per-token KV-transfer term lands both in
    the ledger and between prefill finish and decode availability."""
    cfg = get_config("tinyllama-1.1b")
    request_mod._ids = itertools.count()
    reqs = [Request(prompt=[1] * 64, max_new_tokens=4,
                    arrival_time=0.0) for _ in range(4)]
    rs = ReplicaSet.simulated(cfg, A100, n_prefill=1, n_decode=1,
                              chunk_size=32, n_slots=4, max_prompt_len=64)
    res = rs.run(reqs)
    assert res.n_handoffs == 4
    assert res.kv_transfer_time > 0
    for h in res.handoffs:
        assert h.n_tokens == 64                  # cached prompt KV moved
        assert h.n_bytes == kv_handoff_bytes(cfg, 64)
        assert h.delay == pytest.approx(kv_transfer_time(A100, h.n_bytes))
        assert h.t_installed >= h.t_extracted + h.delay
        assert h.src == "prefill0" and h.dst == "decode0"
    # every request completed with full output on the decode side
    for r in reqs:
        assert len(res.outputs[r.req_id]) == 4
    s = res.summary()
    assert s.n_requests == 4 and s.throughput > 0
    assert set(res.replica_utilization()) == {"prefill0", "decode0"}


def test_disagg_requires_both_pools():
    cfg = get_config("tinyllama-1.1b")
    from repro.serving import serve_disaggregated
    with pytest.raises(ValueError, match="at least one"):
        serve_disaggregated([], [], [])
