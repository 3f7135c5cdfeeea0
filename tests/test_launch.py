"""Sharding-policy unit tests (no 512-device requirement: specs only)."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.launch import shardings as sh
from repro.models import stack


def _pshapes(cfg):
    import functools
    return jax.eval_shape(
        functools.partial(stack.init_params, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))


def test_llama4_expert_parallel_specs():
    cfg = get_config("llama4-maverick-400b-a17b")
    specs = sh.param_pspecs(cfg, _pshapes(cfg))
    lp = specs["groups"][0]
    # experts over data (EP), expert d_ff over model (TP)
    assert lp["ffn"]["w_gate"] == P(None, "data", None, "model")
    assert lp["ffn"]["w_down"] == P(None, "data", "model", None)
    assert lp["ffn"]["router"] == P(None, None, None)
    assert specs["embed"] == P("model", None)


def test_granite_moe_fallback_no_ep():
    cfg = get_config("granite-moe-3b-a800m")     # 40 experts % 16 != 0
    specs = sh.param_pspecs(cfg, _pshapes(cfg))
    lp = specs["groups"][0]
    assert lp["ffn"]["w_gate"] == P(None, None, None, "model")


def test_vision_90b_uses_fsdp():
    cfg = get_config("llama-3.2-vision-90b")
    assert sh.use_fsdp(cfg)
    specs = sh.param_pspecs(cfg, _pshapes(cfg))
    dense_layer = specs["groups"][0]             # first of the 5-layer group
    assert dense_layer["ffn"]["w_gate"] == P(None, "data", "model")
    assert dense_layer["mixer"]["wo"] == P(None, "model", "data")


def test_small_dense_tp_only():
    cfg = get_config("tinyllama-1.1b")
    assert not sh.use_fsdp(cfg)
    specs = sh.param_pspecs(cfg, _pshapes(cfg))
    lp = specs["groups"][0]
    assert lp["ffn"]["w_gate"] == P(None, None, "model")
    assert lp["mixer"]["wq"] == P(None, None, "model")
    assert lp["ln1"] == P(None, None)    # (group axis, d) both replicated


def test_non_divisible_vocab_replicates():
    cfg = get_config("granite-moe-3b-a800m")     # vocab 49155 % 16 != 0
    specs = sh.param_pspecs(cfg, _pshapes(cfg))
    assert specs["embed"] == P(None, None)


def test_shape_support_matrix():
    ok, _ = sh.shape_supported(get_config("mamba2-2.7b"), "long_500k")
    assert ok
    ok, why = sh.shape_supported(get_config("stablelm-12b"), "long_500k")
    assert not ok and "swa" in why
    ok, _ = sh.shape_supported(get_config("stablelm-12b", variant="swa"),
                               "long_500k")
    assert ok
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        for a in ASSIGNED:
            ok, _ = sh.shape_supported(ASSIGNED[a](), s)
            assert ok


def test_paged_pool_leaves_shard_on_production_mesh():
    """The fused pkv pool leaf [n_blocks, nk, 2, bs, hd] must carry
    model-axis specs — the paged cache must not silently replicate
    under TP."""
    import functools
    cfg = get_config("tinyllama-1.1b")
    cshapes = jax.eval_shape(
        functools.partial(stack.init_cache, cfg, 4, 128,
                          dtype=jnp.bfloat16, paged_blocks=33,
                          block_size=16))
    specs = sh.cache_pspecs(cfg, cshapes, rows_axes=None)
    pool = specs["groups"][0]["attn"]
    # tinyllama GQA: nk=4 doesn't divide 16, nor do the 33 blocks; the
    # default "seq" mode falls back to head_dim (64 % 16 == 0)
    assert pool["pkv"] == P(None, None, None, None, None, "model")
    # at tp=2 the kv-head dim shards, whole K/V pairs per head (4 % 2 == 0)
    m2 = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    pool2 = sh.cache_pspecs(cfg, cshapes, rows_axes=None,
                            mesh=m2)["groups"][0]["attn"]
    assert pool2["pkv"] == P(None, None, "model", None, None, None)


def test_policy_is_shared_with_serving_layer():
    """The launch import path must BE the serving policy module — no
    duplicated leaf rules anywhere."""
    from repro.sharding import policy
    assert sh.param_pspecs is policy.param_pspecs
    assert sh.cache_pspecs is policy.cache_pspecs
    assert sh.use_fsdp is policy.use_fsdp
    assert sh.with_sharding is policy.with_sharding


def test_input_shapes_exact():
    assert sh.INPUT_SHAPES["train_4k"] == dict(seq_len=4096,
                                               global_batch=256,
                                               kind="train")
    assert sh.INPUT_SHAPES["prefill_32k"]["global_batch"] == 32
    assert sh.INPUT_SHAPES["decode_32k"]["global_batch"] == 128
    assert sh.INPUT_SHAPES["long_500k"]["seq_len"] == 524288
