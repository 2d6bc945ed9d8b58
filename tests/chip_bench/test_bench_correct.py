"""``correct`` as a run decides it, driven end to end on the CPU at a
tiny size with the chip check skipped: sound runs pass, the control (the
reference in bfloat16) reads above the limit, and a timed path broken
underneath (an answer altered where it is produced; half of each batch
left out) comes out not correct."""

import argparse

import jax
import pytest

from chip_bench import check, faults, run


def run_tiny(root, cell, seed, control=0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=1.5,
                              trace=0, control=control)
    return run.run_cell(args, root=root, require_tpu=False)


@pytest.fixture
def fresh_programs():
    """Faults are planted in traced code: compile anew before and after."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["tiny.tracked", "tiny.offline"])
def test_sound_run_is_correct_and_the_control_is_not(bench_copy, cell):
    res = run_tiny(bench_copy, cell, 2**31 + 99, control=1)
    assert res["correct"], res["checks"]
    assert res["checks"]["peak_mismatch_pct"]["value"] == 0.0
    assert res["checks"]["edge_mismatch_px"]["value"] == 0
    assert (res["control"]["peak_mismatch_pct"]
            > check.LIMITS["peak_mismatch_pct"]
            or res["control"]["edge_mismatch_px"]
            > check.LIMITS["edge_mismatch_px"])
    assert list(res)[-1] == "checks"
    # lateness on a loaded CPU says nothing about correctness
    assert res["attempted"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(bench_copy, monkeypatch,
                                          fresh_programs, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = run_tiny(bench_copy, "tiny.offline", 2**31 + 7)
    assert not res["correct"]
    assert res["checks"]["peak_mismatch_pct"]["value"] > \
        check.LIMITS["peak_mismatch_pct"]
