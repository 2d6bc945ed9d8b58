"""What the program records about itself, read over one window.

The served path (``src/repro/serve/detection.py``) opens host spans named
``service.*`` while a profiler trace is active, stamps every request
(``submitted_at``, ``admitted_at``, ``dispatched_at``, ``finished_at``)
and counts ``edge_pixels`` and ``vote_slots``; the detection program
(``core/plan._detect``) runs every device op under one named scope
(``SCOPES``), which the compiled program's op metadata carries.

A trace's device ops name only the HLO instruction (``%fusion.5 = ...``),
so ``scope_table`` maps instructions to scopes from the compiled text of
the programs the service dispatches, and ``reduce_program`` reads a
trace with it: the ``service.*`` spans of the service thread, the host
time they hold, each idle gap of the device named by the innermost span
around its midpoint, and the device time of the detection programs by
scope.  The readers below turn that, the stamps and the counters into
per-layer metrics; ``program_run.py`` runs a cell and reports them.
"""

from __future__ import annotations

import bisect
import collections
import re

from chip_bench import latency, trace_reduce

SERVICE = "service."
# children of a top-level span in which the host waits, on the device or
# on the prefetch worker, rather than works
WAITS = ("service.block", "service.stage_wait")
SCOPES = ("canny", "compact", "vote", "get_lines", "render")
DETECT_MODULE = "jit__detect"
MODULES_LINE = "XLA Modules"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$")
_HEADER = re.compile(r"^\s*(?:ENTRY\s+)?(%[\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%[\w.\-]+")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")


def scope_of_op_name(op_name: str):
    """The innermost of ``SCOPES`` in an op's name stack, or None."""
    for part in op_name.split(";"):
        found = [p for p in part.split("/") if p in SCOPES]
        if found:
            return found[-1]
    return None


def scope_table(hlo_texts) -> dict:
    """``{instruction name: [(instruction text, scope), ...]}`` over the
    compiled texts of several programs.  An instruction without a scope
    of its own (a fusion whose metadata is empty) takes the commonest
    scope of the computations it calls."""
    table: dict = collections.defaultdict(list)
    for text in hlo_texts:
        # computation name -> [(scope, called computations)]
        comps: dict = collections.defaultdict(list)
        instrs = []
        comp = None
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                h = _HEADER.match(line)
                if h is not None:
                    comp = h.group(1)
                continue
            name, rest = m.groups()
            op = _OP_NAME.search(rest)
            scope = scope_of_op_name(op.group(1)) if op else None
            calls = []
            for single, many in _CALLS.findall(rest):
                calls += [single] if single else [
                    c.strip() for c in many.split(",")]
            comps[comp].append((scope, calls))
            instrs.append((name, rest, scope, calls))
        memo: dict = {}

        def called_scope(c, seen=()):
            if c not in memo:
                votes = collections.Counter()
                for scope, calls in comps.get(c, ()):
                    if scope is None and c not in seen:
                        scope = _first(called_scope(x, seen + (c,))
                                       for x in calls)
                    if scope is not None:
                        votes[scope] += 1
                memo[c] = votes.most_common(1)[0][0] if votes else None
            return memo[c]

        for name, body, scope, calls in instrs:
            if scope is None:
                scope = _first(called_scope(c) for c in calls)
            table[name].append((f"{name} = {body}", scope))
    return dict(table)


def _first(it):
    return next((x for x in it if x is not None), None)


def _operands(text: str) -> tuple:
    """The instruction names an instruction's text refers to, before its
    metadata: the trace prints operands with their shapes, the compiled
    text without, and both print the names."""
    for cut in (", metadata=", ", backend_config="):
        text = text.split(cut)[0]
    return tuple(_NAME.findall(text))


def op_scope(label: str, table: dict):
    """The scope of a device op labelled by its HLO instruction text,
    from the instructions of that name: those with the same result shape
    and operands, else those with the same result shape, else all; None
    where the first of these that holds any disagrees on the scope."""
    cands = table.get(label.split(" = ", 1)[0], ())
    shape, operands = _shape(label), _operands(label)
    for keep in (lambda t: _shape(t) == shape and _operands(t) == operands,
                 lambda t: _shape(t) == shape,
                 lambda t: True):
        scopes = {s for t, s in cands if keep(t)}
        if scopes:
            return scopes.pop() if len(scopes) == 1 else None
    return None


def _shape(text: str) -> str:
    return text.split(" = ", 1)[1].split(" ", 1)[0]


def _clip(a, b, t0, t1):
    return max(a, t0), min(b, t1)


class _Thread:
    """One thread's spans, which nest properly: find the innermost span
    around an instant by bisection."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: (s[0], -s[1]))
        self.starts = [s[0] for s in self.spans]
        self.tops, end = [], None
        for i, (a, b, _) in enumerate(self.spans):
            if end is None or a >= end:
                self.tops.append(i)
                end = b
        self.top_starts = [self.spans[i][0] for i in self.tops]

    def innermost(self, t):
        j = bisect.bisect_right(self.top_starts, t) - 1
        if j < 0 or self.spans[self.tops[j]][1] < t:
            return None
        for k in range(bisect.bisect_right(self.starts, t) - 1,
                       self.tops[j] - 1, -1):
            if self.spans[k][1] >= t:
                return self.spans[k]
        return None


def reduce_program(pd, table: dict | None = None) -> dict:
    """Read a loaded ``ProfileData`` over the window of the harness.

    Returns, in seconds: ``spans`` (per ``service.*`` name, over every
    host thread: count and time inside the window), ``host_s`` (the
    service thread's top-level ``service.*`` spans less their ``WAITS``
    children), ``idle_by_path`` (the device's idle time by the innermost
    span around each gap's midpoint: ``generator``, ``step``,
    ``step>service.split``, ...), ``idle_gaps`` (the longest ten),
    ``scopes`` (device time of ``jit__detect`` ops by scope,
    ``unscoped`` for ops no scope covers) and ``detect_s`` (the device
    time of those programs).  ``table`` is ``scope_table``'s; without
    one, ``scopes`` is empty."""
    window, service, bench, others, device = None, [], [], [], None
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            device = device or plane
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine, marks, holds_window = [], [], False
            for ev in line.events:
                name = ev.name
                if name == trace_reduce.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    holds_window = True
                elif name.startswith(SERVICE):
                    mine.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 name))
                elif name in trace_reduce.HOST_SPANS:
                    marks.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
            if holds_window:
                service, bench = mine, marks
            else:
                others += mine
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} span in the trace")
    if device is None:
        raise ValueError("no /device:TPU plane in the trace")
    t0, t1 = window

    spans = collections.defaultdict(lambda: [0, 0.0])
    for a, b, name in service + others:
        a, b = _clip(a, b, t0, t1)
        if b > a:
            spans[name][0] += 1
            spans[name][1] += (b - a) / 1e9
    service, bench = _Thread(service), _Thread(bench)
    host_ns = 0.0
    for i in service.tops:
        a, b = _clip(*service.spans[i][:2], t0, t1)
        host_ns += max(0.0, b - a)
    for a, b, name in service.spans:
        if name in WAITS:
            a, b = _clip(a, b, t0, t1)
            host_ns -= max(0.0, b - a)

    ops, modules = [], []
    for line in device.lines:
        if line.name == trace_reduce.OPS_LINE:
            ops = list(line.events)
        elif line.name == MODULES_LINE:
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events)
    busy, scopes = [], collections.Counter()
    detect = [(a, b) for a, b, n in modules if n.startswith(DETECT_MODULE)]
    starts = [a for a, _ in detect]
    for ev in ops:
        a, b = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, t0, t1)
        if b <= a:
            continue
        busy.append((a, b))
        if table is None or trace_reduce.CONTAINER.search(ev.name):
            continue
        j = bisect.bisect_right(starts, ev.start_ns) - 1
        if j >= 0 and detect[j][1] >= ev.start_ns:
            scopes[op_scope(ev.name, table) or "unscoped"] += (b - a) / 1e9
    detect_s = trace_reduce.union_length(
        [_clip(a, b, t0, t1) for a, b in detect if b > t0 and a < t1]) / 1e9

    idle = []
    for a, b in trace_reduce.gaps(busy, t0, t1):
        mid = (a + b) / 2
        outer = bench.innermost(mid)
        if outer is None:
            path = "generator"
        else:
            path = outer[2].removeprefix("bench.")
            inner = service.innermost(mid)
            if inner is not None:
                path += ">" + inner[2]
        idle.append(((b - a) / 1e9, path))
    idle.sort(reverse=True)
    by_path: collections.Counter = collections.Counter()
    for s, path in idle:
        by_path[path] += s
    return {
        "window_s": (t1 - t0) / 1e9,
        "spans": {k: v for k, v in sorted(spans.items())},
        "host_s": host_ns / 1e9,
        "idle_by_path": dict(by_path),
        "idle_gaps": [[p, s] for s, p in idle[:10]],
        "scopes": dict(scopes),
        "detect_s": detect_s,
    }


def reduce_file(path: str, table: dict | None = None) -> dict:
    from jax.profiler import ProfileData

    return reduce_program(ProfileData.from_file(str(path)), table)


# --- readers over the run record ``program_run.py`` builds ---------------

def _wait_ms(start: str, end: str, q: float):
    """The ``q``-th percentile of ``end - start`` over the frames due in
    the window and answered in full (None where the program stamps
    neither)."""
    def read(run):
        done = [r.req for r in run["requests"] if r.req.ok]
        if not done or not hasattr(done[0], start) \
                or not hasattr(done[0], end):
            return None
        waits = sorted(getattr(r, end) - getattr(r, start) for r in done)
        return 1e3 * waits[latency.nearest_rank(len(waits), q)]
    return read


admit_wait_p99_ms = _wait_ms("submitted_at", "admitted_at", 99)
fill_wait_p50_ms = _wait_ms("admitted_at", "dispatched_at", 50)
answer_wait_p50_ms = _wait_ms("dispatched_at", "finished_at", 50)


def host_ms_per_frame(run):
    prog, n = run.get("program"), run["answered_in_window"]
    if not prog or not n or not prog["spans"]:
        return None
    return 1e3 * prog["host_s"] / n


def compaction_ms_per_frame(run):
    prog, n = run.get("program"), run["answered_in_window"]
    if not prog or not n or "compact" not in prog["scopes"]:
        return None
    return 1e3 * prog["scopes"]["compact"] / n


def vote_slot_use_pct(run):
    c = run["counters"]
    if not c.get("vote_slots"):
        return None
    return 100.0 * c["edge_pixels"] / c["vote_slots"]

