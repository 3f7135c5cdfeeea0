"""A cell at a size a CPU test can serve: the dense reference's model with
tiny widths, a few short requests, a window of two seconds."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import spec  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def config(qwen: bool = False) -> dict:
    hf = {
        "name": "tiny-qwen2" if qwen else "tiny-llama",
        "reference": "dense_gqa",
        "model_type": "qwen2" if qwen else "llama",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": qwen, "attention_bias": False,
        "hidden_act": "silu",
    }
    hf["program"] = {
        "name": hf["name"], "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab_size": 256, "qkv_bias": qwen, "rope_theta": 10000.0,
        "norm_eps": 1e-5, "tie_embeddings": qwen, "max_seq_len": 128,
        "act": "silu"}
    return hf


def cell(qwen: bool = False, logit_gap_max: float = 0.5) -> spec.Cell:
    traffic = {"arrivals": "poisson",
               "prompt": {"law": "lognormal", "median": 40, "sigma": 0.5,
                          "min": 16, "max": 96},
               "output": {"law": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 1, "max": 32}}
    return spec.Cell(
        name="tiny.test", chips=1, config=config(qwen),
        traffic_name="tiny", traffic=traffic,
        cell={"engine": {"chunk_size": 32, "n_slots": 4, "max_len": 128,
                         "block_size": 16},
              "load": {"rate": 40.0}, "warmup_s": 1.0,
              "check": {"requests": 4, "logit_gap_max": logit_gap_max,
                        "checked_tokens_min": 16}},
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("ttft_per_ktok_ms", "ms"), ("tbt_p99_ms", "ms"),
            ("out_tok_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[{"name": n, "unit": u} for n, u in (
            ("sched_ms_per_step", "ms"), ("decode_lanes_used", "%"),
            ("step_mfu", "%"), ("device_idle_share", "%"))],
        bench_dir=BENCH)


# faults planted under the timed path (``harness.run(server_hook=...)``)
def state_unchanged(srv):
    """The packed step returns the cache it was given: no KV is written."""
    import jax
    eng = srv.engine
    impl = eng._step_impl

    def step(params, pk, cache, key):
        chunk_tok, dec_tok, logits, _ = impl(params, pk, cache, key)
        return chunk_tok, dec_tok, logits, cache

    eng._step = jax.jit(step)


def token_altered(srv):
    """The first decode lane's token is changed where it is produced."""
    eng = srv.engine
    collect, vocab = eng._collect, eng.cfg.vocab_size

    def altered(chunk, decodes, chunk_tok, dec_tok):
        out = collect(chunk, decodes, chunk_tok, dec_tok)
        if decodes:
            rid = decodes[0].req_id
            out[rid] = (out[rid] + 1) % vocab
        return out

    eng._collect = altered


def half_lanes_left_out(srv):
    """Only the first half of the decodes is packed; the rest read what
    the padding lanes computed."""
    eng = srv.engine
    pack = eng._pack

    def half(chunk, decodes, pad_chunk=False):
        return pack(chunk, list(decodes)[:(len(decodes) + 1) // 2],
                    pad_chunk)

    eng._pack = half


FAULTS = {"state_unchanged": state_unchanged,
          "token_altered": token_altered,
          "half_lanes_left_out": half_lanes_left_out}
