"""A configuration, a cell and a per-layer metric are found by name: adding
one takes new files and entries, and no edit to a file already there."""
from __future__ import annotations

import json
import shutil

import chipbench_tiny as T
import pytest

from chipbench import spec


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
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("qwen2-0.5b-copy.long-doc", root=checkout,
                          bench_dir=bench_dir)
    assert cell.config["name"] == "qwen2-0.5b-copy"
    assert cell.traffic["prompt"]["median"] == 900
    assert cell.cell["load"]["rate"] == 5.0
    names = [m["name"] for m in cell.per_layer]
    assert "steps_in_window" in names
    # a metric without ``workloads`` follows the end-to-end metric it
    # moves: out_tok_s is every cell's, ttft_per_ktok_ms only Mistral's
    assert "decode_lanes_used" in names and "step_mfu" not in names
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
