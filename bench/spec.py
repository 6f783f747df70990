"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell's
comparison limits or one metric sits in a file of its own, found by name:

    bench/configs/<config>.json     sizes, model parameters, precision, the
                                    connectivity, weights and delays stated,
                                    and ``"reference": "<model>"``
    bench/models/<model>.py         the neuron and synapse model the check
                                    replays (``bench/models/README.md``)
    bench/traffic/<traffic>.json    chunking, monitors, checkpoints, restore
                                    (and its ``restore_k``)
    bench/limits/<workload>.json    the limit of each number compared
    bench/metrics/<metric>.py       ``read(run) -> float | None``

A later cell adds files and ``BENCHMARK.json`` entries; no file here needs
an edit for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import types
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    model: types.ModuleType

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(bench_dir: str, kind: str, name: str) -> types.ModuleType:
    """The module ``<bench_dir>/<kind>/<name>.py``."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    # a dataclass in the module looks its module up by name
    sys.modules[mod_spec.name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, name: str) -> Callable:
    """``read`` of ``<bench_dir>/metrics/<name>.py``."""
    return _load_module(bench_dir, "metrics", name).read


def load_model(bench_dir: str, config: dict) -> types.ModuleType:
    """The model file the configuration names: ``<bench_dir>/models/
    <config["reference"]>.py``."""
    if "reference" not in config:
        raise KeyError(
            f"configuration {config.get('name')!r} has no \"reference\" key: "
            "it names its model, a file bench/models/<name>.py"
        )
    return _load_module(bench_dir, "models", config["reference"])


def _applies(entry: dict, workload: str, reported: set) -> bool:
    if "workloads" in entry:
        return workload in entry["workloads"]
    if "moves" in entry:
        # a per-layer metric without a list is reported wherever the
        # end-to-end metric it moves is
        return entry["moves"] in reported
    return True


def load_cell(workload: str, root: Optional[str] = None) -> Cell:
    """The cell named ``workload`` with its configuration, model, traffic,
    limits and metric readers.  ``root`` holds ``BENCHMARK.json`` and ``bench/``;
    a missing file raises ``FileNotFoundError``."""
    root = root or os.path.dirname(BENCH_DIR)
    bench_dir = os.path.join(root, "bench")
    bm = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}"
        )
    w = cells[workload]
    config = _load_json(os.path.join(bench_dir, "configs", f"{w['config']}.json"))
    model = load_model(bench_dir, config)
    traffic = _load_json(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(bench_dir, "limits", f"{workload}.json"))
    e2e = [
        Metric(e["name"], e["unit"], load_reader(bench_dir, e["name"]))
        for e in bm["end_to_end"]
        if _applies(e, workload, set())
    ]
    reported = {m.name for m in e2e}
    per_layer = [
        Metric(e["name"], e["unit"], load_reader(bench_dir, e["name"]))
        for e in bm["per_layer"]
        if _applies(e, workload, reported)
    ]
    return Cell(w, config, traffic, limits, e2e, per_layer, model)
