#!/usr/bin/env python3
"""Find the highest stream count a tracked cell sustains, on the chip.

    python3 chip_bench/knee_sweep.py --workload vga-caltech.tracked \\
        --streams 2,4,6,8 --seconds 8 --seed 7

For each stream count, one run of the cell's configuration and mix with
the mix's stream count replaced, in this one process (compiles are shared
after the first).  Prints one JSON line per count: the latency median and
99th percentile, attempted and failed frames, and the backlog trend (the
median latency of the window's last third over its first third; a growing
backlog reads well above 1).  The knee is the highest count whose 99th
percentile (unanswered frames read their wait to the last answer) stays
under the deadline with no frame failed; a tracked cell runs at four
fifths of it.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chip_bench import latency, run  # noqa: E402


def backlog_trend(frames) -> float:
    """Median latency of the last third of the window over the first
    third's (unanswered frames read their wait to the end of the run)."""
    lat = [(f.due, (f.answered if f.answered is not None else float("inf"))
            - f.due) for f in sorted(frames, key=lambda f: f.due)]
    third = max(1, len(lat) // 3)
    first = statistics.median(v for _, v in lat[:third])
    last = statistics.median(v for _, v in lat[-third:])
    return last / first if first > 0 else float("inf")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()
    for n in [int(s) for s in a.streams.split(",")]:
        args = run.parse(["--workload", a.workload, "--seed", str(a.seed),
                          "--seconds", str(a.seconds), "--trace", "0"])
        frames: list = []
        res = run.run_cell(args, t_process=time.perf_counter(), streams=n,
                           keep_frames=frames)
        end = max(f.answered or f.due for f in frames)
        print(json.dumps({
            "streams": n,
            "latency_p50_ms": res["metrics"]["latency_p50_ms"]["value"],
            "latency_p99_ms": 1e3 * latency.percentile_s(frames, 99, end),
            "attempted": res["attempted"], "failed": res["failed"],
            "backlog_trend": backlog_trend(frames),
            "counters": res["counters"], "correct": res["correct"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
