"""Fused paged-KV pool: env-knob validation, layout round-trips, and
engine-level bit-identity of the fused pool against the dense reference
across block sizes plus an extract/install relocation.

The heavier behavioural properties (CoW forks, preemption, prefix reuse)
ride on the fused layout transparently and stay pinned by
test_equivalence / test_prefix_cache / test_disagg; this file pins the
layout contract itself and the env surface added with the fused pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from conftest import cached_model
from repro.core import ChunkWork, DecodeWork, Engine, IterationPlan, \
    plan_chunks
from repro.kernels import ops, ref
from repro.models import blocks as bk
from repro.models import common as cm


# ------------------------------------------------------------- env knobs
def test_backend_env_rejects_unrecognized(monkeypatch):
    monkeypatch.setenv("REPRO_PAGED_ATTN_BACKEND", "triton")
    with pytest.raises(ValueError, match="xla.*pallas|pallas.*xla"):
        bk._paged_attn_backend()


@pytest.mark.parametrize("value", ["xla", "pallas"])
def test_backend_env_accepts_known(monkeypatch, value):
    monkeypatch.setenv("REPRO_PAGED_ATTN_BACKEND", value)
    assert bk._paged_attn_backend() == value


def test_backend_env_defaults_to_xla(monkeypatch):
    monkeypatch.delenv("REPRO_PAGED_ATTN_BACKEND", raising=False)
    assert bk._paged_attn_backend() == "xla"


@pytest.mark.parametrize("value,expect", [
    ("0", False), ("false", False), ("1", True), ("true", True)])
def test_interpret_env_forced(monkeypatch, value, expect):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    assert ops.resolve_interpret() is expect


def test_interpret_env_auto_matches_platform(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert ops.resolve_interpret() is (not on_tpu)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "auto")
    assert ops.resolve_interpret() is (not on_tpu)


def test_interpret_env_rejects_junk(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        ops.resolve_interpret()


@pytest.mark.parametrize("name,fn", [
    ("REPRO_PAGED_KV_PAGES", ops.paged_kv_pages),
    ("REPRO_PAGED_KV_BUFFERS", ops.paged_n_buffers),
    ("REPRO_PAGED_Q_BLOCK", ops.paged_q_block)])
def test_tile_knobs_reject_nonpositive(monkeypatch, name, fn):
    monkeypatch.setenv(name, "0")
    with pytest.raises(ValueError, match=name):
        fn()
    monkeypatch.setenv(name, "3")
    assert fn() == 3


# ------------------------------------------------------- layout contract
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(1, 5), st.integers(1, 4),
       st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_interleave_split_roundtrip(n_blocks, bs, nk, hd, seed):
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((n_blocks, bs, nk, hd)).astype(np.float32)
    v = rng.standard_normal((n_blocks, bs, nk, hd)).astype(np.float32)
    fused = cm.fuse_kv(jnp.asarray(k), jnp.asarray(v))
    assert fused.shape == (n_blocks, bs, nk, 2, hd)
    k2, v2 = cm.split_fused_kv(fused)
    np.testing.assert_array_equal(np.asarray(k2), k)
    np.testing.assert_array_equal(np.asarray(v2), v)
    # pool layout [N, nk, 2, bs, hd]: gathering every block in order
    # restores the per-token rows exactly
    pool = ref.fuse_kv_pools(jnp.asarray(k), jnp.asarray(v))
    assert pool.shape == (n_blocks, nk, 2, bs, hd)
    rows = cm.gather_block_rows(pool, np.arange(n_blocks))
    np.testing.assert_array_equal(
        np.asarray(rows), np.asarray(fused).reshape(n_blocks * bs, nk, 2, hd))


def test_fused_channel_order_is_kv_pairs():
    """K head h of token o in block n lives at pool[n, h, 0, o], its V at
    pool[n, h, 1, o] — the contract the Pallas kernels' one-DMA-per-page
    fetch of ``pool[block, head]`` relies on: head and pair are major
    axes, so the copy never cuts the tiled [bs, hd] minor axes."""
    nk, hd, bs = 3, 4, 2
    k = jnp.arange(bs * nk * hd, dtype=jnp.float32).reshape(1, bs, nk, hd)
    v = -k
    fused = cm.fuse_kv(k, v)
    pool = ref.fuse_kv_pools(k, v)
    for h in range(nk):
        np.testing.assert_array_equal(fused[0, :, h, 0], k[0, :, h])
        np.testing.assert_array_equal(fused[0, :, h, 1], v[0, :, h])
        for o in range(bs):
            np.testing.assert_array_equal(pool[0, h, 0, o], k[0, o, h])
            np.testing.assert_array_equal(pool[0, h, 1, o], v[0, o, h])


# ------------------------------------------- engine-level fused identity
def _pkv_leaves(tree):
    """All fused-pool leaves (cache dict values keyed "pkv"), in order."""
    found = []

    def rec(x):
        if isinstance(x, dict):
            for k, v in x.items():
                found.append(v) if k == "pkv" else rec(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                rec(v)

    rec(tree)
    return found


def _generate(eng, prompt, n_new):
    eng.add_request(0)
    out = []
    for c in plan_chunks(len(prompt), eng.C):
        r = eng.execute(IterationPlan(chunk=ChunkWork(
            0, prompt[c.start:c.start + c.length], c.start, c.is_last)))
        if c.is_last:
            out.append(r[0])
    while len(out) < n_new:
        r = eng.execute(IterationPlan(decodes=[
            DecodeWork(0, out[-1], len(prompt) + len(out) - 1)]))
        out.append(r[0])
    return out


@pytest.mark.parametrize("block_size", [2, 4, 16])
def test_fused_pool_bit_identical_to_dense_across_block_sizes(block_size):
    cfg, model, params = cached_model("tinyllama-1.1b")
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 11).tolist()
    dense = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                   decode_slots=2)
    paged = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                   decode_slots=2, paged=True, block_size=block_size)
    want = _generate(dense, prompt, 6)
    got = _generate(paged, prompt, 6)
    assert got == want    # greedy tokens: bit-identity, not tolerance


def test_extract_install_preserves_fused_pool_rows():
    """Relocating a request between two fused-pool engines with different
    pool geometries is a pure copy: the destination's gathered rows equal
    the source's, and continued greedy decode is unchanged."""
    cfg, model, params = cached_model("tinyllama-1.1b")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 9).tolist()

    ref_eng = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                     decode_slots=2, paged=True, block_size=4)
    want = _generate(ref_eng, prompt, 5)

    src = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                 decode_slots=2, paged=True, block_size=4)
    first = _generate(src, prompt, 1)[0]
    handoff = src.extract_request(0)
    assert handoff.n_blocks > 0

    dst = Engine(cfg, params, n_slots=2, max_len=64, chunk_size=4,
                 decode_slots=2, paged=True, block_size=4, n_blocks=40)
    dst.add_request(0)
    dst.install_request(0, handoff)
    src_pools = _pkv_leaves(src.cache)
    dst_pools = _pkv_leaves(dst.cache)
    assert src_pools and len(src_pools) == len(dst_pools)
    s_tab = np.asarray(src.block_manager.table(0))
    d_tab = np.asarray(dst.block_manager.table(0))
    for sp, dp in zip(src_pools, dst_pools):
        np.testing.assert_array_equal(
            np.asarray(sp)[:, s_tab], np.asarray(dp)[:, d_tab])

    out = [first]
    while len(out) < 5:
        r = dst.execute(IterationPlan(decodes=[
            DecodeWork(0, out[-1], len(prompt) + len(out) - 1)]))
        out.append(r[0])
    assert out == want


# --------------------------------------------- roofline kernel table
def test_roofline_kernel_table_invariants():
    """The gated bandwidth table must keep its ordering claims: fused
    halves DMA descriptors for identical payload (strictly fewer modeled
    HBM bytes), multi-buffering never loses, and fused+multi is the best
    variant of each kernel."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.roofline import kernel_variant_rows

    rows = kernel_variant_rows()      # asserts the invariants internally
    assert len(rows) == 8
    by = {(r["kernel"], r["layout"], r["buffering"]): r for r in rows}
    for k in ("decode", "prefill"):
        assert (by[(k, "fused", "multi")]["throughput"]
                == max(r["throughput"] for r in rows if r["kernel"] == k))
        assert (by[(k, "fused", "single")]["payload_bytes"]
                == by[(k, "split", "single")]["payload_bytes"])
        assert (by[(k, "split", "single")]["n_dma"]
                == 2 * by[(k, "fused", "single")]["n_dma"])
