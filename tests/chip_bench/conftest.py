"""Fixtures of the benchmark's tests: the checkout root on ``sys.path``,
and a copy of the benchmark with tiny cells added as files and entries
only."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIGS = ("tiny", "tiny-x2", "tiny-x4")
# cell: (configuration, mix, chips)
TINY_CELLS = {"tiny.tracked": ("tiny", "tiny-s3", 1),
              "tiny.offline": ("tiny", "tiny-k8", 1),
              "tiny-x2.tracked": ("tiny-x2", "tiny-s3", 2),
              "tiny-x4.offline": ("tiny-x4", "tiny-k16", 4)}


def add_tiny_cells(root: Path) -> None:
    """Add 120x160 configurations (one of them four replicas behind the
    router), two mixes and their cells to the benchmark at ``root`` by
    files and entries alone."""
    bench_dir = root / "chip_bench"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in TINY_CONFIGS:
        shutil.copy(FIXTURES / f"{name}.json",
                    bench_dir / "configs" / f"{name}.json")
        bench["configs"].append({"name": name, "source": "a test size",
                                 "file": f"chip_bench/configs/{name}.json",
                                 "reduced": ["frame"], "why": "tests"})
    for cell, (config, mix, chips) in TINY_CELLS.items():
        shutil.copy(FIXTURES / f"{mix}.json", bench_dir / "mixes")
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "chips": chips,
                                   "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = "tracked" if any(".tracked" in w for w in m["workloads"]) \
                else "offline"
            m["workloads"] += [c for c in TINY_CELLS
                               if c.endswith(f".{kind}")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def make_bench_copy(root: Path) -> Path:
    """A checkout at ``root`` holding the program (linked), the benchmark
    (copied) and the tiny cells."""
    shutil.copytree(ROOT / "chip_bench", root / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(ROOT / "src")
    add_tiny_cells(root)
    return root


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A checkout holding the program (linked), the benchmark (copied)
    and the tiny cells; the environment the harness sets is restored."""
    make_bench_copy(tmp_path)
    for var in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
        monkeypatch.setenv(var, "unset-by-test")
        monkeypatch.delenv(var)
    return tmp_path


@pytest.fixture(scope="module")
def module_bench_copy(tmp_path_factory):
    """The same checkout, shared by one module's tests."""
    return make_bench_copy(tmp_path_factory.mktemp("bench"))
