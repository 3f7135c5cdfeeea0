"""What a cell is made of, found by name.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Everything that belongs to one of them sits in a file of its own
under this benchmark's directory:

* a configuration: the file its entry names (``configs/<name>.json``), and
  the plain reference that file names (``references/<reference>.py``);
* a traffic mix: ``traffic/<traffic>.json``;
* a cell's geometry, load and check: ``workloads/<cell>.json``;
* a per-layer metric: its reader, ``metrics/<metric>.py``, a module with
  ``read(reading) -> float | None``;
* the chips' peaks: ``peaks.json``, keyed by ``device_kind``.

Adding a configuration, a cell or a metric adds files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(
        name or "chipbench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file
    traffic_name: str
    traffic: dict             # traffic/<traffic>.json
    cell: dict                # workloads/<cell>.json
    end_to_end: List[dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    bench_dir: Path

    def reference(self):
        ref = self.config["reference"]
        return load_module(self.bench_dir / "references" / f"{ref}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read


def applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` applies to the cells it lists; one
    without applies to every cell that reports the metric it moves (an
    end-to-end metric without ``workloads`` applies to every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    e2e = [m for m in bench["end_to_end"] if applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic_name=w["traffic"],
                traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                cell=_json(bench_dir / "workloads" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)}); add its published peaks")
    return table[device_kind]


def program_config(config: dict):
    """The program's ModelConfig for a configuration file, checked against
    every published key that shapes what the model computes."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(**config["program"])
    d = config["hidden_size"]
    qwen2 = config["model_type"] == "qwen2"
    want = {
        "family": "dense",
        "d_model": d, "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or d // config[
            "num_attention_heads"],
        "d_ff": config["intermediate_size"],
        "vocab_size": config["vocab_size"],
        "rope_theta": config["rope_theta"],
        "norm_eps": config["rms_norm_eps"],
        "tie_embeddings": config["tie_word_embeddings"],
        "act": config["hidden_act"],
        # Qwen2 has a bias on Q, K and V (and none on the output)
        "qkv_bias": qwen2,
        "sliding_window": None,
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    # what the program cannot compute at all
    for key, ok in (("attention_bias", False), ("mlp_bias", False),
                    ("rope_scaling", None)):
        if config.get(key, ok) != ok:
            bad[key] = ("not served by the program", config[key])
    if config.get("sliding_window") is not None \
            and config.get("use_sliding_window", True):
        bad["sliding_window"] = ("not served by the program",
                                 config["sliding_window"])
    if config["model_type"] not in ("llama", "mistral", "qwen2"):
        bad["model_type"] = ("llama, mistral or qwen2", config["model_type"])
    if bad:
        raise ValueError(f"{config['name']}: program config disagrees with "
                         f"the published keys (program, file): {bad}")
    return cfg
