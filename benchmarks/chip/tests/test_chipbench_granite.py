"""Granite-8B-Code joins the benchmark through its own reference
(``references/granite_dense.py``): it accepts the Llama architecture with
``attention_bias`` and ``mlp_bias``, refuses what the program cannot
compute, counts the biases, names every leaf of the program's tree, and
decides ``correct`` on a tiny Granite-shaped cell served on the CPU, where
a bias the program drops fails the check.

At the tiny cell's size (two layers of width 64, served in bf16, every
bias drawn by the ``bias`` law) sound runs read a widest logit gap of
0.030 at most and a dropped ``bo`` or ``b_down`` 0.49 at least (CPU,
seeds 51-53 and 61-63); the control (the reference in float8) reads
0.28 at least over 256 positions of random tokens (seeds 51-58).  The
limit 0.1 lies between them.
"""
from __future__ import annotations

import copy
import json
import shutil
import time

import chipbench_tiny as T
import jax
import pytest

from chipbench import harness, spec, weights

granite = spec.load_module(T.BENCH / "references" / "granite_dense.py")
dense_gqa = spec.load_module(T.BENCH / "references" / "dense_gqa.py")

NAME = "granite-8b-16l"
LIMIT = 0.1


def _config():
    return json.loads((T.BENCH / "configs" / f"{NAME}.json").read_text())


def test_granite_dense_accepts_granite_8b_16l():
    hf = _config()
    cfg = granite.program_config(hf)
    assert (cfg.qkv_bias, cfg.o_bias, cfg.mlp_bias) == (True, True, True)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (16, 4096, 14336, 49152)
    assert spec.program_config(hf) == cfg
    # the reference every other configuration names refuses it still
    with pytest.raises(ValueError, match="attention_bias"):
        dense_gqa.program_config(hf)


def _set(**keys):
    return lambda hf: hf.update(keys)


def _program(**keys):
    return lambda hf: hf["program"].update(keys)


REFUSED = {
    "rope_scaling": (_set(rope_scaling={"type": "linear", "factor": 2.0}),
                     "rope_scaling"),
    "window": (_set(sliding_window=4096), "sliding_window"),
    "mistral": (_set(model_type="mistral"), "model_type"),
    "qwen2": (_set(model_type="qwen2"), "model_type"),
    "granite": (_set(model_type="granite"), "model_type"),
    "act": (_set(hidden_act="gelu"), "hidden_act"),
    "no_o_bias": (_program(o_bias=False), "o_bias"),
    "no_mlp_bias": (_program(mlp_bias=False), "mlp_bias"),
    "no_attention_bias": (_set(attention_bias=False), "qkv_bias"),
    "layers": (_set(num_hidden_layers=36), "n_layers"),
    "tied": (_program(tie_embeddings=False), "tie_embeddings"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_granite_dense_refuses_what_the_program_cannot_compute(case):
    change, key = REFUSED[case]
    hf = copy.deepcopy(_config())
    change(hf)
    with pytest.raises(ValueError, match=key):
        granite.program_config(hf)


def test_step_terms_count_the_bias_bytes_and_adds():
    hf = _config()
    t = granite.step_terms(hf)
    plain = dense_gqa.step_terms(dict(hf, attention_bias=False,
                                      mlp_bias=False))
    # per layer: q 4096, k and v 1024 each, o 4096; gate and up 14336
    # each, down 4096
    per_layer = 4096 + 2 * 1024 + 4096 + 2 * 14336 + 4096
    assert t["weight_bytes"] - plain["weight_bytes"] == 2 * 16 * per_layer
    assert t["linear_flops_per_token"] \
        - plain["linear_flops_per_token"] == 16 * per_layer
    assert {k: v for k, v in t.items()
            if k not in ("weight_bytes", "linear_flops_per_token")} == \
        {k: v for k, v in plain.items()
         if k not in ("weight_bytes", "linear_flops_per_token")}
    assert t == {"linear_flops_per_token": 6_980_009_984,
                 "attn_flops_per_pair": 262_144,
                 "unembed_flops": 402_653_184,
                 "weight_bytes": 7_383_621_632,
                 "state_bytes_per_token": 65_536,
                 "state_bytes_per_request": 0}


# ------------------------------------------------------ tiny Granite cell
def tiny_config(attention_bias=True, mlp_bias=True) -> dict:
    hf = dict(T.config(), name="tiny-granite", reference="granite_dense",
              attention_bias=attention_bias, mlp_bias=mlp_bias)
    hf["program"] = dict(hf["program"], name="tiny-granite",
                         qkv_bias=attention_bias, o_bias=attention_bias,
                         mlp_bias=mlp_bias)
    return hf


@pytest.mark.parametrize("attention_bias,mlp_bias",
                         [(True, True), (True, False), (False, True)])
def test_weight_specs_name_every_leaf_of_the_program(attention_bias,
                                                     mlp_bias):
    import jax.numpy as jnp
    from repro.models import build_model
    hf = tiny_config(attention_bias, mlp_bias)
    cfg = granite.program_config(hf)
    template = jax.eval_shape(
        lambda k: build_model(cfg).init_params(k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    specs = granite.weight_specs(hf)
    flat = weights.Builder(specs, hf["hidden_size"])(7)
    weights.to_tree(flat, template)          # every leaf, and no other
    law = {n.rsplit("/", 1)[-1]: k for n, _, k in specs}
    biases = ({"bq", "bk", "bv", "bo"} if attention_bias else set()) \
        | ({"b_gate", "b_up", "b_down"} if mlp_bias else set())
    assert {n for n in law if n.startswith("b")} == biases
    assert all(law[b] == "bias" for b in biases)
    assert all(float(abs(flat[n].astype(jnp.float32)).max()) > 0
               for n, _, k in specs if k == "bias")


def tiny_cell(bench_dir=T.BENCH) -> spec.Cell:
    """``chipbench_tiny``'s cell with the tiny Granite configuration."""
    base = T.cell(logit_gap_max=LIMIT)
    return spec.Cell(**dict(vars(base), config=tiny_config(),
                            bench_dir=bench_dir))


def _run(seed):
    return harness.run(tiny_cell(), seed, 2.0, False,
                       t_proc=time.perf_counter(), devices=jax.devices(),
                       peaks=T.PEAKS)


def _drop(name):
    """(module, function, replacement): the program with one bias left
    out, patched in before the run compiles its step."""
    from repro.models import blocks
    from repro.models import common as cm
    mod, fn = {"bo": (blocks, "out_proj"),
               "b_down": (cm, "glu_ffn")}[name]
    orig = getattr(mod, fn)

    def dropped(p, *a, **kw):
        return orig({k: v for k, v in p.items() if k != name}, *a, **kw)
    return mod, fn, dropped


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_sound_tiny_granite_run_is_correct(seed):
    out = _run(seed)
    assert out["correct"] is True
    assert out["check"]["logit_gap_max"]["value"] <= LIMIT


@pytest.mark.parametrize("seed", [51, 52, 53])
def test_the_control_exceeds_the_limit(seed):
    """The reference one precision step lower, on the weights the tiny
    cell serves and 256 positions of random tokens: the widest gap
    between the reference's best logit and that of the control's first
    token (``check.Reference.control_gaps``) exceeds the limit."""
    import jax.numpy as jnp
    hf = tiny_config()
    w = weights.Builder(granite.weight_specs(hf), hf["hidden_size"])(seed)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (256,), 0,
                              hf["vocab_size"])
    pos = jnp.arange(256)
    lg = granite.logits_at(w, hf, toks, pos)
    top = jnp.argmax(granite.logits_at(w, hf, toks, pos, control=True), -1)
    gap = lg.max(-1) - jnp.take_along_axis(lg, top[:, None], 1)[:, 0]
    assert float(gap.max()) > LIMIT


@pytest.mark.parametrize("name", ["bo", "b_down"])
def test_a_dropped_bias_fails_the_check(name, monkeypatch):
    monkeypatch.setattr(*_drop(name))
    out = _run(61)
    assert out["correct"] is False
    assert out["check"]["logit_gap_max"]["value"] > LIMIT


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "benchmarks" / "chip").rglob("*") if p.is_file()}


def test_granite_joins_through_harness_build_by_new_files_alone(tmp_path):
    """A checkout without Granite (the benchmark before it) gains the
    reference, the configuration and the cell as new files and entries;
    the cell loads, the program's server is built and warmed on the
    reference's weights, and no file that was there changes."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(T.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = [bench_dir / "references" / "granite_dense.py",
           bench_dir / "configs" / f"{NAME}.json",
           bench_dir / "workloads" / f"{NAME}.code.json"]
    for p in new:
        p.unlink()
    bench = json.loads((T.ROOT / "BENCHMARK.json").read_text())
    cell_entry = next(w for w in bench["workloads"]
                      if w["name"] == f"{NAME}.code")
    config_entry = next(c for c in bench["configs"] if c["name"] == NAME)
    bench["workloads"].remove(cell_entry)
    bench["configs"].remove(config_entry)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if f"{NAME}.code" in m.get("workloads", []):
            m["workloads"].remove(f"{NAME}.code")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    before = _snapshot(tmp_path)

    for p in new:
        shutil.copy(T.BENCH / p.relative_to(bench_dir), p)
    bench["configs"].append(config_entry)
    bench["workloads"].append(cell_entry)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(f"{NAME}.code", root=tmp_path, bench_dir=bench_dir)
    assert cell.config["reference"] == "granite_dense"
    assert cell.cell["engine"]["n_slots"] == 16
    assert spec.program_config(cell.config, bench_dir).o_bias
    # served at a CPU's size: the same reference on tiny widths
    tiny = tiny_cell(bench_dir)
    srv, params, _ = harness.build(tiny, 2 ** 33 + 5, jax.devices())
    assert float(abs(params["groups"][0]["mixer"]["bo"]).max()) > 0
    assert "b_down" in params["groups"][0]["ffn"]
    harness.free(srv.engine.cache)
    harness.free(params)
    after = _snapshot(tmp_path)
    assert all(after[p] == b for p, b in before.items())
