#!/usr/bin/env python3
"""Run one cell once and read what the program records about itself.

    python3 chip_bench/program_run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--keep <dir>]

The cell is built, warmed and driven as ``run.py`` drives it (the same
configuration, traffic, service and driver).  Besides the cell's
end-to-end metrics it reports the per-layer metrics built on the
program's own records (``program_trace.py``): the request stamps and the
vote slot counters in every run, and with ``--trace 1`` the
``service.*`` spans and the device time by named scope, with the device
idle time named by the innermost span around each gap.  ``--keep``
writes the trace (``trace.xplane.pb.gz``) and the scope table of the
compiled programs (``scopes.json.gz``) there.  No reference comparison
is made: ``run.py`` decides ``correct``.  The last line of standard
output is one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chip_bench import harness, latency, program_trace  # noqa: E402
from chip_bench import registry, run as bench_run, trace_reduce  # noqa: E402
from chip_bench.traffic import generator  # noqa: E402

COUNTERS = harness.COUNTERS + ("edge_pixels", "vote_slots")
# the per-layer metrics on the program's records, by the kind of traffic
# (the suffix splits what moves latency from what moves goodput)
METRICS = {
    "open_streams": ("admit_wait_p99_ms", "fill_wait_p50_ms",
                     "answer_wait_p50_ms", "host_ms_per_frame.tracked",
                     "vote_slot_use_pct.tracked",
                     "compaction_ms_per_frame.tracked"),
    "closed_loop": ("host_ms_per_frame.offline",
                    "vote_slot_use_pct.offline",
                    "compaction_ms_per_frame.offline"),
}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="directory for the trace and the scope table")
    return ap.parse_args(argv)


def program_texts(svc) -> list[str]:
    """The compiled text of each detection program the service has built
    (its warm plan bindings), lowered again with the arguments a dispatch
    ships: the executables this process holds answer it."""
    import numpy as np

    from repro.core.hough import full_corridors
    from repro.core.plan import _detect

    texts = []
    for shape, render, band, fused in sorted(svc._warmed, key=str):
        plan = svc.grids[shape].plan.with_render(render)
        imgs = svc.plans.put(np.zeros((svc.batch_size,) + shape,
                                      np.float32))
        bins = cors = None
        if band is not None:
            plan = plan.with_theta_band(band)
            bins = svc.plans.put(np.zeros(band, np.int32))
        if fused:
            plan = plan.with_fused(svc.fused_corridors)
            cors = svc.plans.put(full_corridors(svc.fused_corridors))
        lowered = _detect.lower(plan.cfg, imgs, bins, cors,
                                tiers=plan.tiers)
        texts.append(lowered.compile().as_text())
    return texts


def run_cell(args, *, root: Path = ROOT, require_tpu: bool = True,
             t_process: float = T_PROCESS) -> dict:
    harness.prepare_environment(root)
    import jax

    cell = registry.find_cell(args.workload, root)
    wl, config, mix = cell["workload"], cell["config"], cell["mix"]
    if require_tpu:
        harness.require_chip(jax, wl["chips"])
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
    traffic = generator.build(config, mix, args.seed)
    devices = jax.devices()
    harness.require_replicas(config, wl["chips"], devices)
    svc = harness.build_service(config, devices)
    driver = harness.Driver(svc, traffic, trace=bool(args.trace))
    harness.warm(svc, driver, traffic)
    table = None
    if args.trace:
        t_texts = time.perf_counter()
        # replicas run the same programs, each on its own device
        table = program_trace.scope_table(
            program_texts(harness.services(svc)[0]))
        print(f"scope table from the compiled programs in "
              f"{time.perf_counter() - t_texts:.1f} s", file=sys.stderr)
    before = harness.counters(svc, COUNTERS, missing=0)

    gc.collect()
    gc.freeze()
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chip_bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    if args.trace:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            driver.run_until(t0 + args.seconds)
            t1 = time.perf_counter()
        jax.profiler.stop_trace()
    else:
        driver.run_until(t0 + args.seconds)
        t1 = time.perf_counter()
    gc.unfreeze()
    counters = harness.counted_since(
        before, harness.counters(svc, COUNTERS, missing=0))
    t_end = driver.finish()
    svc.close()

    recs = harness.frames_in(driver, t0, t1)
    frames = harness.as_latency_frames(recs, traffic.deadline_s)
    run = {
        "kind": traffic.kind, "t0": t0, "t1": t1, "t_end": t_end,
        "window_s": t1 - t0, "setup_s": setup_s, "frames": frames,
        "all_frames": harness.as_latency_frames(driver.sent,
                                                traffic.deadline_s),
        "lags_s": [r.sent - r.due for r in recs],
        "counters": counters, "requests": recs,
        "answered_in_window": bench_run.answered_in(driver.sent, t0, t1),
    }
    result = {"workload": wl["name"], "seed": args.seed,
              "trace": args.trace, "attempted": len(frames),
              "failed": latency.failed(frames)}
    if args.trace:
        path = trace_reduce.find_xplane(trace_dir)
        run["trace"] = trace_reduce.reduce_file(
            path, device_ids=harness.device_ids(svc, devices[0]))
        run["program"] = program_trace.reduce_file(path, table)
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            with open(path, "rb") as src, \
                    gzip.open(keep / "trace.xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
            with gzip.open(keep / "scopes.json.gz", "wt") as dst:
                json.dump(table, dst)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr = run["trace"]
        result["device"] = {"busy_s": tr["busy_s"],
                            "window_s": tr["window_s"],
                            "idle_by_cause": tr["idle_by_cause"]}
        result["program"] = run["program"]
    names = [m["name"] for m in registry.end_to_end_for(cell["bench"],
                                                        wl["name"])]
    names += METRICS[traffic.kind]
    metrics = {}
    for name in names:
        v = registry.metric_reader(name, root)(run)
        if v is not None:
            metrics[name] = v
    result["metrics"] = metrics
    result["counters"] = counters
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except harness.BenchError as e:
        print(f"chip_bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
