"""A configuration, a cell and a per-layer metric are found by name: adding
one takes new files and entries, and no edit to a file already there.
That holds for an architecture the benchmark has never seen: a sparse
mixture of experts and a state-space model join a checkout here through a
reference, a configuration, a traffic mix, a cell and a metric of their
own."""
from __future__ import annotations

import json
import shutil
import textwrap
from types import SimpleNamespace

import chipbench_tiny as T
import pytest

from chipbench import counts, measure, spec


@pytest.fixture()
def checkout(tmp_path):
    shutil.copy(T.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(T.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "benchmarks" / "chip").rglob("*") if p.is_file()}


def test_new_config_cell_and_metric_by_name(checkout):
    bench_dir = checkout / "benchmarks" / "chip"
    before = _snapshot(checkout)
    new_cfg = dict(json.loads((bench_dir / "configs" / "qwen2-0.5b.json")
                              .read_text()), name="qwen2-0.5b-copy")
    (bench_dir / "configs" / "qwen2-0.5b-copy.json").write_text(
        json.dumps(new_cfg))
    (bench_dir / "traffic" / "long-doc.json").write_text(json.dumps(
        {"arrivals": "poisson",
         "prompt": {"law": "lognormal", "median": 900, "sigma": 0.3,
                    "min": 512, "max": 1024},
         "output": {"law": "lognormal", "median": 16, "sigma": 0.3,
                    "min": 8, "max": 64}}))
    (bench_dir / "workloads" / "qwen2-0.5b-copy.long-doc.json").write_text(
        json.dumps({"engine": {"chunk_size": 256, "n_slots": 8,
                               "max_len": 1280},
                    "load": {"rate": 5.0}, "warmup_s": 2,
                    "check": {"requests": 2, "logit_gap_max": 1.0,
                              "checked_tokens_min": 8}}))
    (bench_dir / "metrics" / "steps_in_window.py").write_text(
        "def read(r):\n    return float(len(r.steps)) or None\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "qwen2-0.5b-copy", "source": "https://example.org/x",
        "file": "benchmarks/chip/configs/qwen2-0.5b-copy.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "qwen2-0.5b-copy.long-doc", "config": "qwen2-0.5b-copy",
        "traffic": "long-doc", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "out_tok_s", "workloads": ["qwen2-0.5b-copy.long-doc"]})
    (bench_dir / "metrics" / "steps_per_s.py").write_text(
        "def read(r):\n    return None\n")
    bench["per_layer"].append({
        "name": "steps_per_s", "unit": "steps/s", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "out_tok_s"})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("qwen2-0.5b-copy.long-doc", root=checkout,
                          bench_dir=bench_dir)
    assert cell.config["name"] == "qwen2-0.5b-copy"
    assert cell.traffic["prompt"]["median"] == 900
    assert cell.cell["load"]["rate"] == 5.0
    names = [m["name"] for m in cell.per_layer]
    assert "steps_in_window" in names
    # a metric without ``workloads`` follows the end-to-end metric it
    # moves (out_tok_s is every cell's); one with it, the cells it names
    assert "steps_per_s" in names
    assert "decode_lanes_used" not in names and "step_mfu" not in names
    assert cell.reader("steps_in_window")(type("R", (), {"steps": [1]})) \
        == 1.0
    # a metric without a reader of its own has none
    with pytest.raises(FileNotFoundError):
        cell.reader("decode_lanes_used.long-doc")
    assert cell.reference().weight_specs(cell.config)
    assert spec.program_config(cell.config).d_model == 896
    # the cells already there still load as before, and no file changed
    old = spec.load_cell("mistral-7b-16l.code", root=checkout,
                         bench_dir=bench_dir)
    assert "steps_in_window" not in [m["name"] for m in old.per_layer]
    assert "steps_per_s" in [m["name"] for m in old.per_layer]
    after = _snapshot(checkout)
    assert all(after[p] == b for p, b in before.items())


def test_metrics_by_cell():
    bench = json.loads((T.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert cell.reader(m["name"])
        assert spec.program_config(cell.config).name == cell.config["name"]
        assert cell.cell["check"]["logit_gap_max"] > 0


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


# ------------------------------------------- architectures new to the bench
# Test-only references: each states the program's configuration it checks,
# its weights with their laws, and its counts.  (No forward: these tests
# go as far as ``harness.build``.)
_MOE_REF = """
def program_config(hf):
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(**hf["program"])
    want = {"family": "moe", "d_model": hf["hidden_size"],
            "n_layers": hf["num_hidden_layers"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "d_ff": hf["intermediate_size"],
            "n_experts": hf["num_local_experts"],
            "top_k": hf["num_experts_per_tok"],
            "moe_shared_d_ff": hf["shared_intermediate_size"],
            "vocab_size": hf["vocab_size"]}
    bad = {k: v for k, v in want.items() if getattr(cfg, k) != v}
    if hf["model_type"] != "toy_moe" or bad:
        raise ValueError(bad or hf["model_type"])
    return cfg


def _dims(hf):
    d, nh = hf["hidden_size"], hf["num_attention_heads"]
    return (d, nh, hf["num_key_value_heads"], d // nh,
            hf["intermediate_size"], hf["shared_intermediate_size"],
            hf["num_local_experts"], hf["num_hidden_layers"],
            hf["vocab_size"])


def weight_specs(hf):
    d, nh, nkv, hd, f, fs, E, L, v = _dims(hf)
    g = "groups/0/"
    return [("embed", (v, d), "embed"), ("final_norm", (d,), "norm"),
            ("unembed", (d, v), "dense"),
            (g + "ln1", (L, d), "norm"), (g + "ln2", (L, d), "norm"),
            (g + "mixer/wq", (L, d, nh * hd), "dense"),
            (g + "mixer/wk", (L, d, nkv * hd), "dense"),
            (g + "mixer/wv", (L, d, nkv * hd), "dense"),
            (g + "mixer/wo", (L, nh * hd, d), "dense"),
            (g + "ffn/router", (L, d, E), ("uniform", 0.0, 0.02)),
            (g + "ffn/w_gate", (L, E, d, f), "dense"),
            (g + "ffn/w_up", (L, E, d, f), "dense"),
            (g + "ffn/w_down", (L, E, f, d), "dense"),
            (g + "ffn/shared/w_gate", (L, d, fs), "dense"),
            (g + "ffn/shared/w_up", (L, d, fs), "dense"),
            (g + "ffn/shared/w_down", (L, fs, d), "dense")]


def step_terms(hf):
    d, nh, nkv, hd, f, fs, E, L, v = _dims(hf)
    k = hf["num_experts_per_tok"]
    attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
    active = attn + d * E + 3 * d * f * k + 3 * d * fs
    every = attn + 3 * d * f * E + 3 * d * fs + 2 * d
    return {"linear_flops_per_token": 2 * L * active,
            "attn_flops_per_pair": 4 * nh * hd * L,
            "unembed_flops": 2 * d * v,
            "weight_bytes": 2 * (L * (every + d * E) + d + 2 * d * v),
            "state_bytes_per_token": 4 * nkv * hd * L,
            "state_bytes_per_request": 0}
"""

_SSM_REF = """
def program_config(hf):
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(**hf["program"])
    want = {"family": "ssm", "d_model": hf["hidden_size"],
            "n_layers": hf["num_hidden_layers"],
            "ssm_state": hf["state_size"], "ssm_headdim": hf["head_dim"],
            "ssm_expand": hf["expand"], "ssm_ngroups": hf["n_groups"],
            "ssm_conv_width": hf["conv_kernel"],
            "vocab_size": hf["vocab_size"]}
    bad = {k: v for k, v in want.items() if getattr(cfg, k) != v}
    if hf["model_type"] != "toy_ssm" or bad:
        raise ValueError(bad or hf["model_type"])
    return cfg


def _dims(hf):
    d, N = hf["hidden_size"], hf["state_size"]
    di = hf["expand"] * d
    return (d, di, di // hf["head_dim"], hf["n_groups"] * N,
            hf["conv_kernel"], hf["num_hidden_layers"], hf["vocab_size"])


def weight_specs(hf):
    d, di, nh, gn, W, L, v = _dims(hf)
    m = "groups/0/mixer/"
    return [("embed", (v, d), "embed"), ("final_norm", (d,), "norm"),
            ("unembed", (d, v), "dense"), ("groups/0/ln1", (L, d), "norm"),
            (m + "w_z", (L, d, di), "dense"), (m + "w_x", (L, d, di), "dense"),
            (m + "w_B", (L, d, gn), "dense"), (m + "w_C", (L, d, gn), "dense"),
            (m + "w_dt", (L, d, nh), "dense"),
            (m + "conv_x_w", (L, W, di), "dense"),
            (m + "conv_x_b", (L, di), "bias"),
            (m + "conv_B_w", (L, W, gn), "dense"),
            (m + "conv_B_b", (L, gn), "bias"),
            (m + "conv_C_w", (L, W, gn), "dense"),
            (m + "conv_C_b", (L, gn), "bias"),
            # A = -exp(a_log) in [-16, -1]; softplus(dt_bias) near 0.1
            (m + "a_log", (L, nh), ("uniform", 1.39, 0.8)),
            (m + "dt_bias", (L, nh), ("uniform", -2.25, 0.3)),
            (m + "d_skip", (L, nh), "norm"),
            (m + "norm_w", (L, di), "norm"),
            (m + "w_out", (L, di, d), "dense")]


def step_terms(hf):
    d, di, nh, gn, W, L, v = _dims(hf)
    P, N = hf["head_dim"], hf["state_size"]
    proj = d * (2 * di + 2 * gn + nh) + di * d + W * (di + 2 * gn)
    return {"linear_flops_per_token": 2 * L * (proj + 3 * nh * P * N),
            "attn_flops_per_pair": 0,
            "unembed_flops": 2 * d * v,
            "weight_bytes": 2 * (L * (proj + 2 * di + 3 * nh + d)
                                 + d + 2 * d * v),
            "state_bytes_per_token": 0,
            "state_bytes_per_request": L * (4 * nh * P * N
                                            + 2 * (W - 1) * (di + 2 * gn))}
"""

_BASE = {"hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 256,
         "tie_word_embeddings": False}
NEW = {
    "moe": dict(
        reference=_MOE_REF, scope="experts", wrap=("moe_ffn",),
        config=dict(_BASE, model_type="toy_moe", num_attention_heads=4,
                    num_key_value_heads=2, intermediate_size=32,
                    shared_intermediate_size=64, num_local_experts=8,
                    num_experts_per_tok=2),
        program={"family": "moe", "n_layers": 2, "d_model": 64,
                 "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 32,
                 "n_experts": 8, "top_k": 2, "moe_shared_d_ff": 64,
                 "vocab_size": 256, "max_seq_len": 128}),
    "ssm": dict(
        reference=_SSM_REF, scope="ssm_scan", wrap=("ssd_packed",),
        config=dict(_BASE, model_type="toy_ssm", state_size=16,
                    head_dim=16, expand=2, n_groups=1, conv_kernel=4),
        program={"family": "ssm", "n_layers": 2, "d_model": 64,
                 "n_heads": 0, "n_kv_heads": 0, "head_dim": 0, "d_ff": 0,
                 "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 16,
                 "vocab_size": 256, "max_seq_len": 128}),
}


def _scoped(fn, name):
    import jax

    def wrapped(*a, **kw):
        with jax.named_scope(name):
            return fn(*a, **kw)
    return wrapped


@pytest.mark.parametrize("arch", sorted(NEW))
def test_new_architecture_joins_by_files_alone(checkout, arch, monkeypatch):
    """A configuration of the program's ``moe`` or ``ssm`` family: its
    reference, configuration, traffic, cell and a metric of the scope its
    mechanism adds are new files; the cell loads, its weights are drawn by
    the reference's laws, the program's server is built and warmed on
    them, and a reader finds the new scope's device time."""
    import jax
    from chipbench import harness, program
    from repro.models import blocks
    new = NEW[arch]
    # the program marks the new mechanism as the PR that adds it would
    for fn in new["wrap"]:
        monkeypatch.setattr(blocks, fn,
                            _scoped(getattr(blocks, fn), new["scope"]))
    bench_dir = checkout / "benchmarks" / "chip"
    before = _snapshot(checkout)
    name, cell_name = f"toy-{arch}", f"toy-{arch}.short"
    (bench_dir / "references" / f"toy_{arch}.py").write_text(
        textwrap.dedent(new["reference"]))
    (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(dict(
        new["config"], name=name, reference=f"toy_{arch}",
        program=dict(new["program"], name=name))))
    (bench_dir / "traffic" / "short.json").write_text(json.dumps(
        {"arrivals": "poisson",
         "prompt": {"law": "lognormal", "median": 40, "sigma": 0.5,
                    "min": 16, "max": 96},
         "output": {"law": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 1, "max": 16}}))
    (bench_dir / "workloads" / f"{cell_name}.json").write_text(json.dumps(
        {"engine": {"chunk_size": 32, "n_slots": 4, "max_len": 128,
                    "block_size": 16},
         "load": {"rate": 20.0}, "warmup_s": 1,
         "check": {"requests": 2, "logit_gap_max": 0.5,
                   "checked_tokens_min": 8}}))
    metric = f"{new['scope']}_share"
    (bench_dir / "metrics" / f"{metric}.py").write_text(
        f"def read(r):\n"
        f"    s = r.under([{new['scope']!r}])\n"
        f"    return 100.0 * s / r.traced.busy_s if s else None\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": name, "source": "https://example.org/toy", "reduced": [],
        "file": f"benchmarks/chip/configs/{name}.json", "why": "test"})
    bench["workloads"].append({
        "name": cell_name, "config": name, "traffic": "short", "chips": 1,
        "why": "test"})
    bench["per_layer"].append({
        "name": metric, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "packed step",
        "moves": "out_tok_s", "workloads": [cell_name]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(cell_name, root=checkout, bench_dir=bench_dir)
    assert metric in [m["name"] for m in cell.per_layer]
    assert spec.program_config(cell.config, bench_dir).family == arch
    ref = cell.reference()
    terms = counts.Terms(**ref.step_terms(cell.config))
    srv, params, _ = harness.build(cell, 2 ** 33 + 7, jax.devices())
    # the uniform law's leaf is drawn as the reference states it
    law = {n: k for n, _, k in ref.weight_specs(cell.config)}
    assert any(isinstance(k, tuple) for k in law.values())
    # the packed step's scopes hold the new one beside the old, each op
    # with its whole path (``ffn/experts``)
    scopes = program.step_scopes(srv.engine)
    parts = {lab: set(p.split("/")) for lab, p in scopes.items()}
    assert any(new["scope"] in p for p in parts.values())
    assert "unembed" in set(scopes.values())
    # a trace's operations under those labels: the reader gets the new
    # scope's device time, the kv reader what its scopes hold
    labels = sorted(scopes)
    ops = [(i * 10, i * 10 + 5, f"{lab}{{...}}", scopes[lab])
           for i, lab in enumerate(labels)]
    prog = program.Program(device={"/device:TPU:0": ops})
    red = SimpleNamespace(window=(0, 10 * len(ops)), busy_ns=5 * len(ops),
                          busy_s=5e-9 * len(ops))
    r = measure.Reading(hf=cell.config, terms=terms, decode_lanes=3,
                        chips=1, peaks=T.PEAKS, steps=[], traced=red,
                        scope_s=program.device_by_scope(prog, red.window),
                        ops=program.op_times(prog, red.window))
    n_new = sum(1 for lab in labels if new["scope"] in parts[lab])
    assert cell.reader(metric)(r) == pytest.approx(100 * n_new / len(ops))
    assert {s for _, s, _ in r.ops} == set(scopes.values())
    kv = cell.reader("kv_move_share")(r)
    n_kv = sum(1 for lab in labels
               if parts[lab] & {"kv_read", "kv_write", "kv_carry"})
    assert kv == pytest.approx(100 * n_kv / len(ops))
    assert counts.step_flops(terms, [(0, 32, True)], [5]) > 0
    harness.free(srv.engine.cache)
    harness.free(params)
    # every file that was there is as it was
    after = _snapshot(checkout)
    assert all(after[p] == b for p, b in before.items())
