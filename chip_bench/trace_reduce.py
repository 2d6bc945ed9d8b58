"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps, with ``jax.profiler.ProfileData`` alone.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the chip.  The window is the host span
``bench.window`` that the harness writes around its measured loop; device
intervals are clipped to it.  Where the caller names the devices the
service dispatches to, only their planes are read.  Busy time is the union
of the op intervals; an idle gap is a stretch of the window in which no op
runs, named by the harness span on the host that covers its midpoint
(``bench.submit``, ``bench.step``) or ``generator`` where none does (the
load generator between sends).
"""

from __future__ import annotations

import bisect
import collections
import glob
import re
from pathlib import Path
from typing import Iterable, Optional

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.submit", "bench.step")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# Control-flow ops span the ops of their bodies, which the line lists too:
# they count toward busy time but not toward any op's own time.
CONTAINER = re.compile(r" (conditional|while|call)\(")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def label(name: str, stats: dict) -> str:
    """The op's name and its string metadata, for kernel matching."""
    extra = [str(v) for v in stats.values() if isinstance(v, str)]
    return " ".join([name] + extra)


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Stretches of ``[t0, t1]`` covered by no interval."""
    out, cur = [], t0
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def reduce_profile(pd, *, device_ids: Optional[Iterable[int]] = None,
                   top: int = 10) -> dict:
    """``{window_s, busy_s, n_devices, ops: {label: s}, idle_gaps}`` from a
    loaded ``ProfileData``, over the planes of ``device_ids`` (every
    device plane where that is None).  Times in seconds; busy time
    averaged over those devices, op time summed over them, idle gaps
    those of the first."""
    window: Optional[tuple[float, float]] = None
    host: list[tuple[float, float, str]] = []
    devices = []
    wanted = None if device_ids is None else set(device_ids)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            if wanted is None or int(plane.name.rsplit(":", 1)[1]) in wanted:
                devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in HOST_SPANS:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        which = "" if wanted is None else f" of devices {sorted(wanted)}"
        raise ValueError(f"no /device:TPU plane{which} in the trace")
    t0, t1 = window
    ops: collections.Counter = collections.Counter()
    busy_ns = 0.0
    first_gaps = None
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, t0)
                b = min(ev.start_ns + ev.duration_ns, t1)
                if b <= a:
                    continue
                ivs.append((a, b))
                lab = label(ev.name, _stats(ev))
                if not CONTAINER.search(lab):
                    ops[lab] += (b - a) / 1e9
        busy_ns += union_length(ivs)
        if first_gaps is None:
            first_gaps = gaps(ivs, t0, t1)
    host.sort()
    starts = [a for a, _, _ in host]

    def cause(mid: float) -> str:
        # the harness's spans come from one thread and never overlap
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and host[i][1] >= mid:
            return host[i][2].removeprefix("bench.")
        return "generator"

    idle = sorted(((b - a) / 1e9, cause((a + b) / 2)) for a, b in first_gaps)
    idle.reverse()
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / len(devices) / 1e9,
        "n_devices": len(devices),
        "ops": dict(ops),
        "idle_gaps": [[name, s] for s, name in idle[:top]],
        "idle_by_cause": _sum_by(idle),
    }


def _sum_by(idle) -> dict:
    out: collections.Counter = collections.Counter()
    for s, name in idle:
        out[name] += s
    return dict(out)


def reduce_file(path: str, **kw) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(Path(path))), **kw)


def kernel_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the ops whose label matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for lab, s in reduced["ops"].items() if rx.search(lab))


def top_ops(reduced: dict, n: int = 10) -> list:
    """The ``n`` ops with the most device time, labels without layouts."""
    return [[re.sub(r"\{[^{}]*\}", "", lab)[:100], s] for lab, s in
            sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:n]]
