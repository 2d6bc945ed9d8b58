#!/usr/bin/env python3
"""Faults planted underneath the timed path, to see ``correct`` fail.

    python3 chip_bench/faults.py --workload vga-caltech.offline \\
        --seed 5 --seconds 5

runs the cell once per fault, in this one process on the chip it is
started on, with the fault planted in the program under the harness, and
prints one JSON line per fault: ``correct`` and the numbers compared.
The benchmark's tests plant the same faults at a test size on the CPU.

* ``shift_a_peak``: an answer altered where it is produced (every peak
  moved one rho bin in ``get_lines``);
* ``drop_half_the_batch``: the second half of every batch's frames
  zeroed on their way to the device.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chip_bench import harness, run  # noqa: E402


def shift_a_peak(setattr_):
    plan = importlib.import_module("repro.core.plan")
    original = plan.get_lines

    def altered(votes, **kw):
        lines, valid, peaks = original(votes, **kw)
        return lines, valid, peaks.at[..., 0].add(1.0)

    setattr_(plan, "get_lines", altered)


def drop_half_the_batch(setattr_):
    from repro.core.plan import PlanCache

    original = PlanCache.put

    def put(self, x):
        if isinstance(x, np.ndarray) and x.ndim == 3:
            x = x.copy()
            x[x.shape[0] // 2:] = 0
        return original(self, x)

    setattr_(PlanCache, "put", put)


FAULTS = {"shift_a_peak": shift_a_peak,
          "drop_half_the_batch": drop_half_the_batch}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args()
    try:
        harness.prepare_environment()
    except harness.BenchError as e:
        print(f"chip_bench: {e}", file=sys.stderr)
        return 2
    import jax

    for name, plant in FAULTS.items():
        undo = []

        def setattr_(obj, attr, value):
            undo.append((obj, attr, getattr(obj, attr)))
            setattr(obj, attr, value)

        jax.clear_caches()          # the faults live in traced code
        plant(setattr_)
        try:
            args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                              "--seconds", str(a.seconds), "--trace", "0"])
            res = run.run_cell(args, t_process=time.perf_counter())
        except harness.BenchError as e:
            print(f"chip_bench: {e}", file=sys.stderr)
            return 2
        finally:
            for obj, attr, value in reversed(undo):
                setattr(obj, attr, value)
        print(json.dumps({"fault": name, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
