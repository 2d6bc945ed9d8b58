#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the window's start):
the cell's frames are generated from the seed, the service is built as the
configuration deploys it, every program the window can take is compiled
(or read from the compile cache in ``.jax_cache/`` of the checkout), and
the cell's traffic runs for the mix's ``warm_s`` so trackers are warm.
Then the traffic runs for ``--seconds`` (the window), is served to its
end, and a seeded sample of the answers is compared with the plain
reference (``chip_bench/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports the cell's per-layer metrics.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it (``checks``) and as the last lines of
standard error.  A host without a TPU, a forced kernel impl other than
Pallas, a directory without the program, or a configuration whose
``replicas`` outnumber the cell's chips or the host's devices exits
non-zero and prints no result.  ``device`` reports the cell's chips: their
count, and the peak memory of the fullest; the trace is read on the chips
the service dispatches to.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chip_bench import check, harness, latency, registry, work  # noqa: E402
from chip_bench import trace_reduce  # noqa: E402
from chip_bench.traffic import generator  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference in "
                         "bfloat16) on the sampled frames")
    return ap.parse_args(argv)


def answered_in(recs, t0, t1) -> int:
    """Frames answered (in full or degraded) inside ``[t0, t1)``."""
    return sum(1 for r in recs if r.req.is_terminal and r.req.status.served
               and t0 <= r.req.finished_at < t1)


def run_cell(args, *, root: Path = ROOT, require_tpu: bool = True,
             t_process: float = T_PROCESS, streams=None,
             keep_frames: list | None = None) -> dict:
    """One run of one cell; returns the result object.  ``streams``
    overrides the mix's stream count and ``keep_frames`` receives the
    window's frames (the knee sweep)."""
    harness.prepare_environment(root)
    import jax

    cell = registry.find_cell(args.workload, root)
    wl, config, mix = cell["workload"], cell["config"], cell["mix"]
    if require_tpu:
        harness.require_chip(jax, wl["chips"])
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
    compiles = harness.CompileCounter(jax)
    traffic = generator.build(config, mix, args.seed, streams=streams)
    devices = jax.devices()
    harness.require_replicas(config, wl["chips"], devices)
    svc = harness.build_service(config, devices)
    recorder = harness.DispatchRecorder(svc)
    driver = harness.Driver(svc, traffic, trace=bool(args.trace))
    harness.warm(svc, driver, traffic)
    before = harness.counters(svc)
    built_setup = compiles.snapshot()
    n_log = len(recorder.log)

    # What set-up left behind is not garbage the window should scan again.
    gc.collect()
    gc.freeze()
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chip_bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the harness's spans, little else
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
    in_window = compiles.snapshot().get("built", 0) - built_setup.get(
        "built", 0)
    counters = harness.counted_since(before, harness.counters(svc))
    window_dispatches = [d for d in recorder.log[n_log:] if d.at < t1]
    t_end = driver.finish()

    # the cell's chips: the peak is the fullest chip's
    chips = devices[:wl["chips"]]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in chips]
    peaks = [b for b in peaks if b is not None]
    dev = chips[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(chips),
              "memory_peak_bytes": max(peaks) if peaks else None}

    recs = harness.frames_in(driver, t0, t1)
    frames = harness.as_latency_frames(recs, traffic.deadline_s)
    attempted, failed = len(frames), latency.failed(frames)
    if keep_frames is not None:
        keep_frames.extend(frames)
    unanswered = sum(1 for r in driver.sent if not r.req.is_terminal)
    run = {
        "kind": traffic.kind,
        "t0": t0, "t1": t1, "t_end": t_end, "window_s": t1 - t0,
        "setup_s": setup_s,
        "frames": frames,
        "all_frames": harness.as_latency_frames(driver.sent,
                                                traffic.deadline_s),
        "lags_s": [r.sent - r.due for r in recs],
        "counters": counters,
        "answered_in_window": answered_in(driver.sent, t0, t1),
    }
    bench = cell["bench"]
    breakdown = None
    if not args.trace:
        wanted = registry.end_to_end_for(bench, wl["name"])
    else:
        wanted = registry.per_layer_for(bench, wl["name"])
        t_reduce = time.perf_counter()
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir),
            device_ids=harness.device_ids(svc, devices[0]))
        print(f"trace reduced in {time.perf_counter() - t_reduce:.1f} s",
              file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
        by_uid = {r.uid: r for r in driver.sent}
        run.update(trace=reduced,
                   work=work.count(window_dispatches, by_uid),
                   peak=work.peaks_for(dev.device_kind))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": trace_reduce.top_ops(reduced),
                     "idle_gaps": reduced["idle_gaps"]}
        idle_by_cause = reduced["idle_by_cause"]
    metrics = {}
    for m in wanted:
        v = registry.metric_reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_check = time.perf_counter()
    readings = check.compare(recs, recorder.by_uid(), args.seed,
                             control=bool(args.control))
    correct, checks = check.verdict(readings, unanswered)
    print(f"setup {setup_s:.1f} s, window {t1 - t0:.1f} s, served to the "
          f"end {t_end - t1:.1f} s later, compared in "
          f"{time.perf_counter() - t_check:.1f} s", file=sys.stderr)
    svc.close()
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
        result["idle_by_cause"] = idle_by_cause
    result["setup_s"] = setup_s
    result["compiles_in_window"] = in_window
    result["setup_compiles"] = built_setup
    result["counters"] = counters
    if args.control:
        result["control"] = readings["control"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args)
    except harness.BenchError as e:
        print(f"chip_bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
