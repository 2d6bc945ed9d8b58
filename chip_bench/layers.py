"""Shared readers behind the per-layer metric files in ``metrics/``.

Each reader takes the run record that ``run.py`` builds in a traced run
and returns a number, or None where the run has nothing to read.
"""

from __future__ import annotations

from chip_bench import latency, trace_reduce, work

# Device ops of each kernel: the chip's trace names an op by its HLO
# instruction, "%<kernel>.<n> = <shape> custom-call(...)" for a Pallas call.
KERNELS = {
    "hough_vote": r"^%hough_vote(\.\d+)? = .*custom-call\(",
    "canny": r"^%(conv2d_gemm|fused_weights)(\.\d+)? = .*custom-call\(",
}


def device_idle_pct(run):
    """Mean idle share over the chips that served."""
    tr = run["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def device_ms_per_frame(run):
    """Device time a frame costs, summed over the chips that served."""
    tr, n = run["trace"], run["answered_in_window"]
    return 1e3 * tr["busy_s"] * tr["n_devices"] / n if n else None


def roofline(kernel):
    def read(run):
        seconds = trace_reduce.kernel_seconds(run["trace"], KERNELS[kernel])
        return work.roofline_pct(run["work"][kernel], seconds, run["peak"])
    return read


def dispatch_pct(counter):
    def read(run):
        c = run["counters"]
        return 100.0 * c[counter] / c["dispatches"] if c["dispatches"] else None
    return read


def gen_lag_p99_ms(run):
    lags = sorted(run["lags_s"])
    if run["kind"] != "open_streams" or not lags:
        return None
    return 1e3 * lags[latency.nearest_rank(len(lags), 99)]


def frames_per_dispatch(run):
    c = run["counters"]
    return c["completed"] / c["dispatches"] if c["dispatches"] else None
