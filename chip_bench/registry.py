"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, named in ``BENCHMARK.json``:

* a configuration is the JSON file its entry names (``configs/*.json``);
* a traffic mix ``<traffic>`` is ``chip_bench/mixes/<traffic>.json``;
* a metric ``<name>``, end-to-end or per-layer, is
  ``chip_bench/metrics/<name>.py``, a module with
  ``read(run) -> float | None`` over the run record ``run.py`` builds.

A new cell or metric is new files plus entries; no code here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration and mix loaded:
    ``{"workload": entry, "config": {...}, "mix": {...}, "bench": {...}}``."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((Path(root) / entry["file"]).read_text())
    mix_path = Path(root) / HERE.name / "mixes" / f"{cell['traffic']}.json"
    mix = json.loads(mix_path.read_text())
    return {"workload": cell, "config": config, "mix": mix, "bench": bench}


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    """The end-to-end metrics the cell reports."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics the cell reports: those that list it, and
    those without a list whose moved metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metric_reader(name: str, root: Path = ROOT
                  ) -> Callable[[dict], Optional[float]]:
    path = Path(root) / HERE.name / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
