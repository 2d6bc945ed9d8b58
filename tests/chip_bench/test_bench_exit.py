"""A run that cannot measure the chip exits non-zero and prints no
result: no TPU, a forced kernel impl other than Pallas, or a directory
holding only the benchmark's files."""

import os
import shutil
import subprocess
import sys

import pytest

from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

ARGS = ["--workload", "vga-caltech.tracked", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def run(cwd, **env):
    e = dict(os.environ)
    e.update(env)
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_bench/run.py", *ARGS],
                          cwd=cwd, env=e, capture_output=True, text=True,
                          timeout=120)


def assert_refused(p):
    assert p.returncode != 0, p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_tpu_exits_nonzero():
    p = run(ROOT, JAX_PLATFORMS="cpu")
    assert_refused(p)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_forced_impl_exits_nonzero(impl):
    p = run(ROOT, JAX_PLATFORMS="cpu", REPRO_KERNEL_IMPL=impl)
    assert_refused(p)
    assert "Pallas" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(ROOT / "chip_bench", tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests" / "chip_bench",
                    tmp_path / "tests" / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = run(tmp_path, JAX_PLATFORMS="cpu")
    assert_refused(p)
    assert "src/repro" in p.stderr
