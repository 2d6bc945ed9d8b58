"""Drive one cell through ``DetectionService`` and measure it.

The served entry is the one a user calls: ``DetectionService.submit``,
then ``step()`` until the request is terminal.  A configuration with
``"replicas": N`` (N > 1) deploys N of them behind the program's router,
``ShardedDetectionService``, one on each of the cell's first N chips; the
entry is then the router's ``submit`` and ``step``.  The harness sends the
cell's traffic (``traffic/generator.py``) from this one thread, steps the
service between sends, and records for every frame when it was due, when
it was sent and when its terminal answer came.  Around each call into the
service it keeps host spans (``bench.submit``, ``bench.step``), written
into the profiler's trace in a traced run.

The program under test is imported from ``src/repro`` of the checkout;
nothing else of it is used but its service, its counters and, at the call
into the plan (``PlanCache.put``), the gate and corridors each dispatch
ships, which the reference needs.  Behind a router every replica is read
alike (``services``): counters are summed over the replicas and each
replica's dispatches are recorded at its own plan cache.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

from chip_bench import latency
from chip_bench.traffic import generator

ROOT = Path(__file__).resolve().parents[1]
COUNTERS = ("dispatches", "completed", "gated_dispatches",
            "fused_dispatches", "completed_late", "shed_deadline",
            "downshifted", "served_coast", "rejected_queue_full",
            "rejected_invalid")
# the router's own counters, read beside the sums where there is a router
ROUTER_COUNTERS = ("routed", "session_migrations", "session_failovers",
                   "requeued")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, wrong impl, no
    program)."""


def prepare_environment(root: Path = ROOT) -> None:
    """Before JAX is imported: the program must be there, the compile
    cache lives in the checkout, and no kernel impl may be forced."""
    if not (root / "src" / "repro").is_dir():
        raise BenchError(f"no src/repro under {root}: run from a checkout "
                         f"of the repository")
    forced = os.environ.get("REPRO_KERNEL_IMPL")
    if forced and forced != "pallas":
        raise BenchError(f"REPRO_KERNEL_IMPL={forced!r}: the benchmark "
                         f"runs the Pallas kernels only")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))


def require_chip(jax, chips: int) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")


def _plain(v):
    """JSON lists as the tuples the service takes (bucket shapes)."""
    return tuple(_plain(x) for x in v) if isinstance(v, list) else v


def require_replicas(config: dict, chips: int, devices: Sequence) -> None:
    """A configuration deploys ``"replicas": N`` services (1 without the
    key), one on each of the cell's chips: a cell whose ``chips`` is not
    N, or a host with fewer than N devices, is refused."""
    n = config.get("replicas", 1)
    if type(n) is not int or n < 1:
        raise BenchError(f"replicas {n!r}: a whole number, at least 1")
    if n != chips:
        raise BenchError(f"the cell's chips ({chips}) differ from the "
                         f"configuration's replicas ({n})")
    if len(devices) < n:
        raise BenchError(f"the configuration deploys {n} replicas, more "
                         f"than the devices JAX sees ({len(devices)})")


def build_service(config: dict, devices: Sequence):
    """The service as the configuration deploys it.  Every key of its
    ``service`` object is a ``DetectionService`` option, passed as it
    stands, except two that name objects: ``hough`` (the
    ``HoughConfig`` fields of the ``PipelineConfig``) and ``steering``
    (true: the default ``ControlConfig``).  Every other option is the
    program's default.

    A top-level ``"replicas": N`` above 1 deploys N such services behind
    ``ShardedDetectionService`` with the router's defaults, one on each
    of ``devices[:N]`` (``require_replicas`` checks N first)."""
    n = config.get("replicas", 1)
    from repro.core import ControlConfig, HoughConfig, PipelineConfig
    from repro.serve.detection import DetectionService

    kw = {k: _plain(v) for k, v in config["service"].items()}
    cfg = PipelineConfig(hough=HoughConfig(**kw.pop("hough", {})))
    if kw.pop("steering", False):
        kw["steering"] = ControlConfig()
    if n == 1:
        return DetectionService(cfg, **kw)
    from repro.serve.fleet import ShardedDetectionService

    return ShardedDetectionService(cfg, n_replicas=n,
                                   devices=list(devices[:n]), **kw)


def services(svc) -> list:
    """The ``DetectionService``s that serve: ``svc`` itself, or each
    replica's behind a router, in replica order."""
    replicas = getattr(svc, "replicas", None)
    return [svc] if replicas is None else [r.service for r in replicas]


def device_ids(svc, default) -> list[int]:
    """The id of the device each service dispatches to (``default``: the
    device a service without one of its own uses)."""
    return [(default if s.device is None else s.device).id
            for s in services(svc)]


def counters(svc, names: Sequence[str] = COUNTERS, missing=None) -> dict:
    """Each counter of ``names`` summed over the services; a name a
    service lacks raises, or reads ``missing`` where that is given.
    Behind a router also the router's own counters and
    ``replica_dispatches``, each replica's dispatch count."""
    svcs = services(svc)

    def read(s, k):
        return getattr(s, k) if missing is None else getattr(s, k, missing)

    out = {k: sum(read(s, k) for s in svcs) for k in names}
    if svcs[0] is not svc:
        out.update({k: getattr(svc, k) for k in ROUTER_COUNTERS})
        out["replica_dispatches"] = [s.dispatches for s in svcs]
    return out


def counted_since(before: dict, after: dict) -> dict:
    """What each counter of ``counters`` counted between two readings."""
    return {k: ([a - b for a, b in zip(v, before[k])]
                if isinstance(v, list) else v - before[k])
            for k, v in after.items()}


class CompileCounter:
    """Programs built and persistent-cache hits and misses, as JAX's
    monitoring events report them."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.counts = collections.Counter()
        backend = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **kw):
            if event == backend:
                self.counts["built"] += 1

        def on_event(event, **kw):
            if event.endswith("cache_hits"):
                self.counts["cache_hits"] += 1
            elif event.endswith("cache_misses"):
                self.counts["cache_misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return dict(self.counts)


@dataclasses.dataclass
class Dispatch:
    at: float
    uids: list
    replica: int = 0
    bins: Optional[np.ndarray] = None
    cors: Optional[np.ndarray] = None


class DispatchRecorder:
    """Records, at the call into the plan, which requests each dispatch
    carried and the gate and corridors it shipped, at each replica's own
    plan cache.  ``step`` puts the slot buffer first, then the gate, then
    the corridors, then runs the plan; a replica's step runs whole before
    the next replica's."""

    def __init__(self, svc):
        self.log: list[Dispatch] = []
        for i, s in enumerate(services(svc)):
            s.plans.put = self._hook(i, s)

    def _hook(self, replica: int, svc):
        put = svc.plans.put

        def record(x):
            for g in svc.grids.values():
                if x is g.staged:
                    self.log.append(Dispatch(
                        time.perf_counter(),
                        [r.uid for r in g.slots if r is not None], replica))
                    break
            else:
                if self.log and isinstance(x, np.ndarray):
                    if x.dtype == np.int32 and x.ndim == 1:
                        self.log[-1].bins = x.copy()
                    elif x.ndim == 2 and x.shape[-1] == 4:
                        self.log[-1].cors = x.copy()
            return put(x)

        return record

    def by_uid(self) -> dict:
        return {u: d for d in self.log for u in d.uids}


@dataclasses.dataclass
class Sent:
    uid: int
    key: tuple            # which frame of the traffic (work counts cache
                          # one reference edge map per key)
    frame: np.ndarray
    due: float
    sent: float
    req: object


class Driver:
    """Sends a cell's traffic from this thread and steps the service."""

    def __init__(self, svc, traffic: generator.Traffic, *, trace=False):
        from repro.serve.detection import DetectionRequest

        self.svc = svc
        self.traffic = traffic
        self.request = DetectionRequest
        self.sent: list[Sent] = []
        self.uid = 0
        self.trace = trace
        self.next_k = [0] * len(traffic.streams)
        self.origin: Optional[float] = None
        self.pool_i = 0
        self.outstanding: list[Sent] = []
        if trace:
            from jax.profiler import TraceAnnotation
            self.annotation = TraceAnnotation

    def _call(self, span: str, fn, *args):
        if self.trace:
            with self.annotation(span):
                return fn(*args)
        return fn(*args)

    def _send(self, frame, key, due, session=None) -> Sent:
        now = time.perf_counter()
        dl = self.traffic.deadline_s
        req = self.request(
            uid=self.uid, frame=frame, session_id=session,
            deadline_s=None if dl is None else dl - (now - due))
        self.uid += 1
        rec = Sent(req.uid, key, frame, due, now, req)
        self.sent.append(rec)
        self._call("bench.submit", self.svc.submit, req)
        return rec

    def run_until(self, t_stop: float) -> None:
        """Send what falls due before ``t_stop`` and step meanwhile."""
        if self.traffic.kind == "open_streams":
            self._streams_until(t_stop)
        else:
            self._closed_until(t_stop)

    def _streams_until(self, t_stop: float) -> None:
        streams = self.traffic.streams
        if self.origin is None:
            self.origin = time.perf_counter()
        while True:
            now = time.perf_counter()
            pending = False
            for s, st in enumerate(streams):
                while True:
                    k = self.next_k[s]
                    due = self.origin + st.due(k)
                    if due >= t_stop:
                        break
                    pending = True
                    if due > now:
                        break
                    key, frame = st.frame(k)
                    self._send(frame, key, due, st.session)
                    self.next_k[s] = k + 1
            if now >= t_stop and not pending:
                return
            self._call("bench.step", self.svc.step)

    def _closed_until(self, t_stop: float) -> None:
        while time.perf_counter() < t_stop:
            for j in range(self.traffic.in_flight):
                if (j >= len(self.outstanding)
                        or self.outstanding[j].req.is_terminal):
                    key, frame = self.traffic.pool_frame(self.pool_i)
                    self.pool_i += 1
                    rec = self._send(frame, key, time.perf_counter())
                    if j >= len(self.outstanding):
                        self.outstanding.append(rec)
                    else:
                        self.outstanding[j] = rec
            self._call("bench.step", self.svc.step)

    def finish(self, max_wait_s: float = 60.0) -> float:
        """Serve what is queued or in flight; returns the end time."""
        t_give_up = time.perf_counter() + max_wait_s
        while time.perf_counter() < t_give_up:
            if all(r.req.is_terminal for r in self.sent):
                break
            self.svc.step(flush=True)
        return time.perf_counter()


def frames_in(driver: Driver, t0: float, t1: float) -> list[Sent]:
    """The attempted frames: due (open loop) or sent (closed) in the
    window."""
    return [r for r in driver.sent if t0 <= r.due < t1]


def as_latency_frames(recs: list[Sent], deadline_s: Optional[float]
                      ) -> list[latency.Frame]:
    """A frame's deadline runs from when it was due, not when it was
    sent."""
    out = []
    for r in recs:
        req = r.req
        answered = req.finished_at if (req.is_terminal
                                       and req.status.served) else None
        out.append(latency.Frame(
            due=r.due, sent=r.sent, answered=answered, done=req.ok,
            deadline=None if deadline_s is None else r.due + deadline_s))
    return out


def warm(svc, driver: Driver, traffic: generator.Traffic) -> None:
    """Compile what the window can take and settle the trackers.

    Tracked traffic can take every binding of every bucket (the gate and
    corridors of a warm tracker, a ladder downshift under load), so all
    are compiled, and one batch is served downshifted into each smaller
    bucket so the result path at that shape is built too; behind a router
    each replica does so on its own device, submitted to it directly.
    Sessionless traffic without deadlines takes only the full sweep at
    its own shape.  Then the cell's traffic runs until two grids of
    answers a replica have come back (the first answers build the result
    path) and ``warm_s`` more.
    """
    svcs = services(svc)
    if traffic.kind == "open_streams":
        frame = traffic.streams[0].frame(0)[1]
        for s in svcs:
            s.warm_up()
            native = s.bucket_for(frame)
            for bucket in s.buckets:
                if bucket[0] < native[0]:
                    for i in range(s.batch_size):
                        s.submit(driver.request(uid=-1 - i, frame=frame),
                                 force_bucket=bucket)
                    s.run()
    need = 2 * svcs[0].batch_size * len(svcs)
    while sum(r.req.is_terminal for r in driver.sent) < need:
        driver.run_until(time.perf_counter() + 0.25)
    driver.run_until(time.perf_counter() + traffic.warm_s)
