"""What a cell is made of, found by name.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Everything that belongs to one of them sits in a file of its own
under this benchmark's directory:

* a configuration: the file its entry names (``configs/<name>.json``), and
  the plain reference that file names (``references/<reference>.py``).
  The reference is all the benchmark knows of an architecture; it
  provides

  - ``program_config(config)``: the program's configuration, checked
    against every published key that shapes the computation, refusing
    what the program cannot compute;
  - ``weight_specs(config)``: [(name, shape, law)] of every weight, the
    names the program's parameter paths (``chipbench.weights``);
  - ``step_terms(config)``: the fields of ``chipbench.counts.Terms``;
  - ``logits_at(weights, config, tokens, positions, control=False)``: the
    plain forward that decides ``correct`` (``chipbench.check``);
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
        return reference(self.config, self.bench_dir)

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


def reference(config: dict, bench_dir: Path = BENCH_DIR):
    """The configuration's reference module, ``references/<reference>.py``."""
    return load_module(bench_dir / "references" / f"{config['reference']}.py")


def program_config(config: dict, bench_dir: Path = BENCH_DIR):
    """The program's configuration for a configuration file, as the
    configuration's reference checks it against the published keys."""
    return reference(config, bench_dir).program_config(config)
