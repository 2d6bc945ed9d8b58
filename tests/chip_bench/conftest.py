"""Fixtures of the benchmark's tests: the checkout root on ``sys.path``,
and a copy of the benchmark with a tiny cell added as files only."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CELLS = {"tiny.tracked": "tiny-s3", "tiny.offline": "tiny-k8"}


def add_tiny_cells(root: Path) -> None:
    """Add a 120x160 configuration, two mixes and their cells to the
    benchmark at ``root`` by files and entries alone."""
    bench_dir = root / "chip_bench"
    shutil.copy(FIXTURES / "tiny.json", bench_dir / "configs" / "tiny.json")
    for mix in TINY_CELLS.values():
        shutil.copy(FIXTURES / f"{mix}.json", bench_dir / "mixes")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "a test size",
                             "file": "chip_bench/configs/tiny.json",
                             "reduced": ["frame"], "why": "tests"})
    for cell, mix in TINY_CELLS.items():
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": mix, "chips": 1,
                                   "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "tracked" if any(".tracked" in w for w in m["workloads"]) \
                else "offline"
            m["workloads"].append(f"tiny.{kind}")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A checkout holding the program (linked), the benchmark (copied)
    and the tiny cells; the environment the harness sets is restored."""
    shutil.copytree(ROOT / "chip_bench", tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "src").symlink_to(ROOT / "src")
    add_tiny_cells(tmp_path)
    for var in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
        monkeypatch.setenv(var, "unset-by-test")
        monkeypatch.delenv(var)
    return tmp_path
