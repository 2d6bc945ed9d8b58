"""Degradation ladder + fault-injection harness (fleet robustness).

Every test drives ``DetectionService`` on a :class:`VirtualClock` — the
ladder decisions (downshift / coast / shed), the injected faults (stager
death, dispatch failure, stalls, clock jumps, corrupt frames), and the
SLO accounting are all pure functions of the driven schedule.  The
contract under test is the robustness contract of ``ISSUE``-grade
overload: every request reaches an *explicit* terminal status (no
hangs), coast answers run zero detection dispatches, and degraded
answers stay in native coordinates.
"""

import dataclasses
import math

import numpy as np
import pytest

from repro.core import HoughConfig, PipelineConfig
from repro.core.plan import DetectionResult, downsample2x, downshift_frame
from repro.core.tracking import LaneTracker, TrackerConfig
from repro.runtime import HeartbeatMonitor, ServiceFaultInjector, WorkerFailure
from repro.serve.detection import (
    SHED_ONLY, DegradationPolicy, DetectionRequest, DetectionService,
    PrefetchStager, RequestStatus, VirtualClock, upscale_result,
)

pytestmark = pytest.mark.fleet

BUCKETS = ((96, 128), (120, 160))


def _cfg() -> PipelineConfig:
    return PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto"))


def make_svc(**kw) -> DetectionService:
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("batch_size", 1)
    kw.setdefault("clock", VirtualClock())
    kw.setdefault("prefetch", False)
    return DetectionService(_cfg(), **kw)


def _frame(h: int, w: int, seed: int = 0) -> np.ndarray:
    from repro.data import make_scenario
    return make_scenario("straight", h, w, seed=seed).image


def _ground_estimate(svc, clock, shape, dt, uid0=900):
    """Measure the bucket's EMA at ``dt`` via warm no-deadline traffic.

    The ``is_ready`` poll is held off meanwhile: a batch the device has
    already finished would be retired by it, and such a sample may not
    raise the estimate, so whether ``dt`` above the default estimate
    registers would depend on how fast the host ran the batch."""
    reap, svc._reap = svc._reap, lambda: None
    warms = [DetectionRequest(uid=uid0 + u, frame=_frame(*shape, seed=u))
             for u in range(3)]
    try:
        for w in warms:
            svc.submit(w)
            svc.step()
            clock.advance(dt)
        svc.drain()
    finally:
        svc._reap = reap
    assert all(w.ok for w in warms)
    assert svc.grids[shape].est_measured


def _warm_session(svc, sid, n=8, shape=(96, 128), uid0=800):
    """Feed ``n`` real frames so the session's tracker earns the coast
    (confirmed + ``hits >= coast_hits`` under the default config)."""
    for i in range(n):
        r = DetectionRequest(uid=uid0 + i, frame=_frame(*shape),
                             session_id=sid)
        svc.submit(r)
        svc.run()
        assert r.ok and r.tracks
    assert svc.sessions[sid].can_coast()


# --- status classification (satellite: is_terminal routing) -----------------


def test_status_classification_single_source():
    """Every status classifies through RequestStatus properties, and the
    terminal set partitions exactly into served vs refused."""
    for s in RequestStatus:
        if s is RequestStatus.PENDING:
            assert not s.terminal and not s.served and not s.refused
        else:
            assert s.terminal
            assert s.served != s.refused   # exact partition
    r = DetectionRequest(uid=0, frame=np.zeros((96, 128), np.float32))
    assert not r.is_terminal and not r.done
    r.status = RequestStatus.DEGRADED_COAST
    assert r.is_terminal and r.done           # done is the alias
    assert r.served and r.degraded and not r.ok
    r.status = RequestStatus.FAILED
    assert r.is_terminal and not r.served and r.status.refused


# --- virtual clock edge cases (satellite) -----------------------------------


def test_virtual_clock_rejects_backward_jump():
    clock = VirtualClock()
    clock.advance(2.0)
    assert clock.jump_to(5.0) == 5.0
    assert clock.jump_to(5.0) == 5.0          # zero-width jump is fine
    with pytest.raises(ValueError):
        clock.jump_to(4.0)
    with pytest.raises(AssertionError):
        clock.advance(-0.1)
    assert clock() == 5.0                      # rejected jumps change nothing


def test_forward_jump_expires_whole_edf_wave_in_one_step():
    """One large jump past every queued deadline: a single step() sheds
    the entire wave — no per-entry stepping, no hang."""
    clock = VirtualClock()
    svc = make_svc(buckets=((96, 128),), clock=clock)
    reqs = [DetectionRequest(uid=i, frame=_frame(96, 128, seed=i),
                             deadline_s=float(1 + i))
            for i in range(4)]
    for r in reqs:
        svc.submit(r)
    clock.jump_to(100.0)
    svc.step()
    assert all(r.status is RequestStatus.DEADLINE_EXCEEDED for r in reqs)
    assert svc.shed_deadline == 4 and svc.dispatches == 0


def test_zero_duration_dispatch_does_not_poison_ema():
    """Back-to-back dispatches with no clock motion (dt == 0) must leave
    the EMA unmeasured — a zero estimate would make every deadline look
    feasible forever."""
    svc = make_svc(buckets=((96, 128),))
    svc.detect_many([_frame(96, 128, seed=s) for s in range(4)])
    g = svc.grids[(96, 128)]
    assert not g.est_measured and g.est_s > 0.0   # prior intact


# --- downshift rung ---------------------------------------------------------


def test_downsample2x_and_downshift_frame_shapes():
    img = np.arange(120 * 160, dtype=np.float32).reshape(120, 160)
    half = downsample2x(img)
    assert half.shape == (60, 80) and half.dtype == np.float32
    # 2x2 mean of the top-left block
    assert half[0, 0] == pytest.approx(img[:2, :2].mean())
    odd = downsample2x(np.ones((5, 7), np.float32))
    assert odd.shape == (3, 4) and np.allclose(odd, 1.0)  # edge-replicated
    out, factor = downshift_frame(img, (96, 128))
    assert factor == 2 and out.shape == (60, 80)
    same, factor1 = downshift_frame(img, (120, 160))
    assert factor1 == 1 and same.shape == (120, 160)


def test_upscale_result_maps_coordinates_exactly():
    """The pool chain maps native centers x -> (x - c)/factor with
    c = (factor-1)/2; upscale_result must apply the exact inverse."""
    peaks = np.array([[18.25, 0.0], [10.0, math.pi / 2]], np.float32)
    lines = np.array([[18.25, 0.0, 18.25, 59.0]], np.float32)
    res = DetectionResult(
        lines, np.array([1], np.int32), peaks,
        np.zeros((60, 80), np.float32), None,
    )
    up = upscale_result(res, 2, 120, 160)
    # vertical line (theta=0): rho' = 2*18.25 + 0.5*(cos0 + sin0) = 37.0
    assert up.peaks[0, 0] == pytest.approx(37.0)
    assert up.peaks[0, 1] == pytest.approx(0.0)
    # horizontal line (theta=pi/2): same offset math on the y axis
    assert up.peaks[1, 0] == pytest.approx(2 * 10.0 + 0.5)
    np.testing.assert_allclose(up.lines, 2.0 * lines + 0.5)
    assert up.edges.shape == (120, 160)


def test_ladder_downshifts_instead_of_shedding():
    """A deadline hopeless at the native bucket but feasible one bucket
    down is served DEGRADED_DOWNSHIFT from the smaller grid, in native
    coordinates and close to the full-fidelity answer; the identical
    traffic with the ladder off is shed."""
    frame = _frame(120, 160)
    full = make_svc().detect_many([frame])[0]

    clock = VirtualClock()
    svc = make_svc(clock=clock)
    _ground_estimate(svc, clock, (120, 160), dt=0.2)
    req = DetectionRequest(uid=0, frame=frame, deadline_s=0.05)
    svc.submit(req)
    svc.run()
    assert req.status is RequestStatus.DEGRADED_DOWNSHIFT
    assert req.served and req.degraded and not req.ok and req.done
    assert req.downshift == 2 and req.bucket == (96, 128)
    assert svc.downshifted == 1 and svc.served_downshift == 1
    assert svc.dispatch_log[-1][0] == (96, 128)
    # native-coordinate answer: the strongest peak agrees with the
    # full-fidelity run to within the pooled quantization
    assert req.result.edges.shape == (120, 160)
    pa = np.asarray(req.result.peaks)[0]
    pb = np.asarray(full.result.peaks)[0]
    assert abs(pa[0] - pb[0]) < 6.0 and abs(pa[1] - pb[1]) < 0.12

    clock2 = VirtualClock()
    off = make_svc(clock=clock2, ladder=False)
    _ground_estimate(off, clock2, (120, 160), dt=0.2)
    req2 = DetectionRequest(uid=0, frame=frame, deadline_s=0.05)
    off.submit(req2)
    off.run()
    assert req2.status is RequestStatus.DEADLINE_EXCEEDED


def test_downshift_respects_policy_and_floor():
    """allow_downshift=False and a floor above every smaller bucket both
    exhaust the rung; with no session to coast on, the request sheds."""
    for policy in (SHED_ONLY,
                   DegradationPolicy(floor=(120, 160))):
        clock = VirtualClock()
        svc = make_svc(clock=clock)
        _ground_estimate(svc, clock, (120, 160), dt=0.2)
        req = DetectionRequest(uid=0, frame=_frame(120, 160),
                               deadline_s=0.05, policy=policy)
        svc.submit(req)
        svc.run()
        assert req.status is RequestStatus.DEADLINE_EXCEEDED
        assert svc.downshifted == 0 and svc.served_coast == 0


# --- coast rung -------------------------------------------------------------


def test_coast_rung_serves_from_tracker_with_zero_dispatches():
    """An overloaded session request is answered from the tracker's
    prediction: DEGRADED_COAST, no Hough dispatch, non-mutating."""
    clock = VirtualClock()
    svc = make_svc(buckets=((96, 128),), clock=clock)
    _warm_session(svc, "cam0")
    _ground_estimate(svc, clock, (96, 128), dt=0.05)
    before = svc.dispatches
    tracker_state = [dataclasses.replace(t)
                     for t in svc.sessions["cam0"]._tracks]
    req = DetectionRequest(uid=0, frame=_frame(96, 128),
                           session_id="cam0", deadline_s=0.02)
    svc.submit(req)
    svc.run()
    assert req.status is RequestStatus.DEGRADED_COAST
    assert req.tracks and req.result is None
    assert svc.dispatches == before            # ZERO detection dispatches
    assert svc.served_coast == 1 and svc.shed_deadline == 0
    # the tracker itself did not advance (the coast is a pure prediction)
    for t0, t1 in zip(tracker_state, svc.sessions["cam0"]._tracks):
        assert t0.rho == t1.rho and t0.misses == t1.misses
    slo = svc.session_slo("cam0")
    assert slo.served_coast == 1 and slo.served_full == 8
    assert slo.miss_rate == 0.0 and slo.degraded_rate == pytest.approx(1 / 9)


def test_coast_budget_exhausts_like_a_real_dropout():
    """Consecutive coasts burn the tracker's miss budget (max_misses);
    past it the rung refuses until a real frame re-grounds the session."""
    clock = VirtualClock()
    svc = make_svc(buckets=((96, 128),), clock=clock)
    _warm_session(svc, "cam0")
    _ground_estimate(svc, clock, (96, 128), dt=0.05)
    budget = svc.tracker_cfg.max_misses
    coasted = []
    for i in range(budget + 1):
        r = DetectionRequest(uid=10 + i, frame=_frame(96, 128),
                             session_id="cam0", deadline_s=0.02)
        svc.submit(r)
        svc.run()
        coasted.append(r.status)
    assert coasted[:budget] == [RequestStatus.DEGRADED_COAST] * budget
    assert coasted[budget] is RequestStatus.DEADLINE_EXCEEDED
    # a real frame resets the coast budget
    real = DetectionRequest(uid=50, frame=_frame(96, 128),
                            session_id="cam0")
    svc.submit(real)
    svc.run()
    assert real.ok
    again = DetectionRequest(uid=51, frame=_frame(96, 128),
                             session_id="cam0", deadline_s=0.02)
    svc.submit(again)
    svc.run()
    assert again.status is RequestStatus.DEGRADED_COAST


def test_coast_respects_policy():
    clock = VirtualClock()
    svc = make_svc(buckets=((96, 128),), clock=clock)
    _warm_session(svc, "cam0")
    _ground_estimate(svc, clock, (96, 128), dt=0.05)
    req = DetectionRequest(uid=0, frame=_frame(96, 128),
                           session_id="cam0", deadline_s=0.02,
                           policy=DegradationPolicy(allow_coast=False))
    svc.submit(req)
    svc.run()
    assert req.status is RequestStatus.DEADLINE_EXCEEDED


# --- priority-tiered shedding (last rung) -----------------------------------


def test_eviction_displaces_strictly_lower_tier_only():
    svc = make_svc(buckets=((96, 128),), max_queue=1)
    lo = DetectionRequest(uid=0, frame=_frame(96, 128), priority=2)
    svc.submit(lo)
    hi = DetectionRequest(uid=1, frame=_frame(96, 128, seed=1), priority=0)
    assert svc.submit(hi) is RequestStatus.PENDING   # displaced the tier-2
    assert lo.status is RequestStatus.QUEUE_FULL and svc.evicted == 1
    peer = DetectionRequest(uid=2, frame=_frame(96, 128, seed=2), priority=0)
    assert svc.submit(peer) is RequestStatus.QUEUE_FULL  # no lower tier left
    assert svc.evicted == 1 and svc.rejected_queue_full == 2
    svc.run()
    assert hi.ok


def test_no_eviction_with_ladder_off():
    svc = make_svc(buckets=((96, 128),), max_queue=1, ladder=False)
    lo = DetectionRequest(uid=0, frame=_frame(96, 128), priority=2)
    svc.submit(lo)
    hi = DetectionRequest(uid=1, frame=_frame(96, 128, seed=1), priority=0)
    assert svc.submit(hi) is RequestStatus.QUEUE_FULL   # old contract
    assert lo.status is RequestStatus.PENDING and svc.evicted == 0
    svc.run()
    assert lo.ok


# --- prefetch-worker death (satellite: explicit error, never a hang) --------


def test_stager_death_mid_stream_surfaces_explicitly():
    """Kill the worker thread mid-stream: the fatal task's future and
    every queued future resolve with WorkerFailure, and later stage()
    calls raise immediately — no caller can block forever."""
    calls = []

    def hook():
        calls.append(1)
        if len(calls) == 2:
            raise WorkerFailure("injected death")

    st = PrefetchStager(fault_hook=hook)
    try:
        f1 = st.stage(lambda x: x + 1, 1)
        assert f1.result(timeout=10.0) == 2
        futs = []
        try:
            for i in range(4):       # one of these is fatal
                futs.append(st.stage(lambda x: x, i))
        except WorkerFailure:
            pass                     # raised at the submit site: also fine
        st._thread.join(timeout=10.0)
        assert not st.alive
        for f in futs:               # every accepted future RESOLVES
            with pytest.raises(WorkerFailure):
                f.result(timeout=10.0)
        with pytest.raises(WorkerFailure):
            st.stage(lambda: 0)
    finally:
        st.close()


def test_stager_heartbeat_on_virtual_clock():
    clock = VirtualClock()
    reg: dict = {}
    st = PrefetchStager(heartbeat_registry=reg, clock=clock, worker_id="w0")
    try:
        assert st.stage(lambda: 42).result(timeout=10.0) == 42
        mon = HeartbeatMonitor(reg, timeout_s=1.0, clock=clock)
        assert mon.all_alive()
        clock.advance(5.0)          # silence past the liveness deadline
        assert "w0" in mon.dead_workers()
    finally:
        st.close()


def test_service_restarts_dead_stager_and_still_answers():
    """An injected stager death inside the service path costs overlap,
    never correctness: the service restarts the worker (new heartbeat
    incarnation) and every request completes DONE."""
    faults = ServiceFaultInjector(kill_stager_at=(0,))
    svc = make_svc(buckets=((96, 128),), prefetch=True, faults=faults)
    frames = [np.repeat(_frame(96, 128, seed=s)[..., None], 3, axis=2)
              for s in range(4)]    # RGB: staging does real work
    reqs = [DetectionRequest(uid=i, frame=f) for i, f in enumerate(frames)]
    for r in reqs:
        svc.submit(r)
    svc.run()
    svc.close()
    assert all(r.ok for r in reqs)
    assert svc.stager_deaths == 1
    assert "detection-prefetch-0" in svc.heartbeats


def test_stager_restart_budget_falls_back_to_synchronous():
    faults = ServiceFaultInjector(kill_stager_at=(0, 1, 2, 3, 4, 5))
    svc = make_svc(buckets=((96, 128),), prefetch=True, faults=faults,
                   max_stager_restarts=1)
    frames = [np.repeat(_frame(96, 128, seed=s)[..., None], 3, axis=2)
              for s in range(6)]
    reqs = [DetectionRequest(uid=i, frame=f) for i, f in enumerate(frames)]
    for r in reqs:
        svc.submit(r)
        svc.run()                   # interleave so each death is observed
    svc.close()
    assert all(r.ok for r in reqs)  # synchronous fallback, same answers
    assert not svc.prefetch         # budget spent: prefetch disabled
    assert svc.stager_deaths == 2   # 1 restart + the one that broke it


# --- dispatch faults, stalls, corrupt frames, clock jumps -------------------


def test_injected_dispatch_failure_is_explicit_and_isolated():
    faults = ServiceFaultInjector(fail_dispatch_at=(0,))
    svc = make_svc(buckets=((96, 128),), faults=faults)
    a = DetectionRequest(uid=0, frame=_frame(96, 128))
    b = DetectionRequest(uid=1, frame=_frame(96, 128, seed=1))
    svc.submit(a)
    svc.submit(b)
    svc.run()
    assert a.status is RequestStatus.FAILED and a.result is None
    assert b.ok                      # the fault does not leak forward
    assert svc.dispatch_faults == 1 and svc.completed == 1
    assert all(len(e) == 3 for e in svc.dispatch_log)


def test_injected_stall_lands_late_but_never_poisons_the_ema():
    clock = VirtualClock()
    faults = ServiceFaultInjector(stall_dispatch_at=(1,), stall_s=1.0)
    svc = make_svc(buckets=((96, 128),), clock=clock, faults=faults)
    w = DetectionRequest(uid=0, frame=_frame(96, 128))     # dispatch 0: cold
    svc.submit(w)
    svc.run()
    stalled = DetectionRequest(uid=1, frame=_frame(96, 128, seed=1),
                               deadline_s=0.5)             # dispatch 1: stall
    svc.submit(stalled)
    svc.run()
    assert stalled.ok and stalled.missed_deadline          # served, late
    assert stalled.finished_at == pytest.approx(1.0)
    assert svc.completed_late == 1
    assert not svc.grids[(96, 128)].est_measured   # stall sample excluded


def test_corrupt_frame_refuses_or_coasts():
    # no session to fall back on: explicit INVALID_FRAME
    faults = ServiceFaultInjector(corrupt_frame_uids=(0,))
    svc = make_svc(buckets=((96, 128),), faults=faults)
    bad = DetectionRequest(uid=0, frame=_frame(96, 128))
    ok = DetectionRequest(uid=1, frame=_frame(96, 128, seed=1))
    svc.submit(bad)
    svc.submit(ok)
    svc.run()
    assert bad.status is RequestStatus.INVALID_FRAME and bad.result is None
    assert ok.ok and svc.rejected_invalid == 1

    # a warmed session coasts through the bad capture instead
    clock = VirtualClock()
    faults2 = ServiceFaultInjector(corrupt_frame_uids=(0,))
    svc2 = make_svc(buckets=((96, 128),), clock=clock, faults=faults2)
    _warm_session(svc2, "cam0")
    req = DetectionRequest(uid=0, frame=_frame(96, 128), session_id="cam0")
    svc2.submit(req)
    svc2.run()
    assert req.status is RequestStatus.DEGRADED_COAST and req.tracks


def test_injected_clock_jump_expires_the_wave():
    clock = VirtualClock()
    faults = ServiceFaultInjector(clock_jump_at_step=(0,), clock_jump_s=50.0)
    svc = make_svc(buckets=((96, 128),), clock=clock, faults=faults)
    reqs = [DetectionRequest(uid=i, frame=_frame(96, 128, seed=i),
                             deadline_s=float(1 + i)) for i in range(3)]
    for r in reqs:
        svc.submit(r)
    svc.run()
    assert all(r.status is RequestStatus.DEADLINE_EXCEEDED for r in reqs)
    assert clock() >= 50.0 and svc.dispatches == 0


def test_every_fault_class_resolves_terminal():
    """The headline robustness contract: under a combined fault storm
    every submitted request reaches an explicit terminal status."""
    clock = VirtualClock()
    faults = ServiceFaultInjector(
        kill_stager_at=(1,), fail_dispatch_at=(2,),
        stall_dispatch_at=(4,), corrupt_frame_uids=(3, 7),
        clock_jump_at_step=(6,), clock_jump_s=0.5,
    )
    svc = make_svc(buckets=((96, 128),), prefetch=True, clock=clock,
                   faults=faults)
    reqs = []
    for i in range(12):
        f = _frame(96, 128, seed=i % 3)
        if i % 2:
            f = np.repeat(f[..., None], 3, axis=2)   # exercise staging
        reqs.append(DetectionRequest(
            uid=i, frame=f,
            deadline_s=2.0 if i % 3 == 0 else None,
        ))
    for r in reqs:
        svc.submit(r)
    svc.run()
    svc.close()
    assert all(r.is_terminal for r in reqs)          # no hangs, ever
    for r in reqs:
        assert r.served != r.status.refused          # exact partition
        assert (r.result is not None) == (
            r.status in (RequestStatus.DONE, RequestStatus.DEGRADED_DOWNSHIFT)
        )


# --- tracker coast-prediction unit ------------------------------------------


def test_predict_tracks_matches_real_coast_and_does_not_mutate():
    cfg = TrackerConfig()
    tr = LaneTracker(cfg)
    peaks = np.array([[40.0, 0.3], [90.0, 1.2]], np.float32)
    for k in range(cfg.coast_hits + 1):
        tr.step(peaks + np.float32(k) * np.array([[0.5, 0.0]] * 2,
                                                 np.float32))
    assert tr.can_coast()
    before = [dataclasses.replace(t) for t in tr._tracks]
    k = 2
    predicted = tr.predict_tracks(k)
    # non-mutating
    for t0, t1 in zip(before, tr._tracks):
        assert t0.rho == t1.rho and t0.drho == t1.drho
        assert t0.misses == t1.misses and t0.age == t1.age
    # bit-identical to actually coasting k empty frames
    twin = LaneTracker(cfg)
    for k2 in range(cfg.coast_hits + 1):
        twin.step(peaks + np.float32(k2) * np.array([[0.5, 0.0]] * 2,
                                                    np.float32))
    coasted = None
    for _ in range(k):
        coasted = twin.step(np.zeros((0, 2), np.float32))
    assert len(predicted) == len(coasted)
    for p, c in zip(sorted(predicted, key=lambda t: t.track_id),
                    sorted(coasted, key=lambda t: t.track_id)):
        assert p.rho == pytest.approx(c.rho)
        assert p.theta == pytest.approx(c.theta)
        assert p.misses == c.misses
    # beyond the miss budget the coast refuses
    assert tr.predict_tracks(cfg.max_misses + 1) == []


# --- union theta-band gated dispatch (PR 7) ---------------------------------


def _stream_cycle(svc, clock, *, session="ego", n=14, uid0=0):
    """Drive one session through a drive cycle, one frame per dispatch."""
    from repro.data import make_drive_cycle
    cycle = make_drive_cycle("straight", n, 120, 160, seed=0)
    reqs = []
    for fr in cycle.frames:
        req = DetectionRequest(uid=uid0 + fr.t, frame=fr.scene.image,
                               session_id=session)
        svc.submit(req)
        svc.run()
        clock.advance(0.01)
        reqs.append(req)
    return reqs


def test_union_gate_bitexact_with_full_sweep():
    """At full coverage the gated dispatch is bit-exact with the full
    sweep — the gate is a speedup, never a correctness dependence."""
    clock_g, clock_f = VirtualClock(), VirtualClock()
    gated = make_svc(clock=clock_g)                  # gate_band=40 default
    full = make_svc(clock=clock_f, gate_band=None)
    got = _stream_cycle(gated, clock_g)
    ref = _stream_cycle(full, clock_f)
    # the session confirms within a few frames; after that every
    # single-slot grid is fully covered and the gate engages
    assert gated.gated_dispatches > 0
    assert full.gated_dispatches == 0
    for g, f in zip(got, ref):
        assert g.ok and f.ok
        np.testing.assert_array_equal(np.asarray(g.result.peaks),
                                      np.asarray(f.result.peaks))
        np.testing.assert_array_equal(np.asarray(g.result.lines),
                                      np.asarray(f.result.lines))
        np.testing.assert_array_equal(np.asarray(g.result.valid),
                                      np.asarray(f.result.valid))
    gated.close()
    full.close()


def test_union_gate_requires_every_slot_covered():
    """A grid with any sessionless (or tracker-less) slot full-sweeps:
    gating is all-or-nothing per dispatch."""
    clock = VirtualClock()
    svc = make_svc(clock=clock, batch_size=2)
    # warm the session's tracker to gating health on single-slot grids
    for t in range(6):
        svc.submit(DetectionRequest(uid=t, frame=_frame(120, 160, seed=0),
                                    session_id="ego"))
        svc.submit(DetectionRequest(uid=100 + t,
                                    frame=_frame(120, 160, seed=0),
                                    session_id="ego"))
        svc.run()
        clock.advance(0.01)
    assert svc.sessions["ego"].gate_bins(svc.cfg.hough.n_theta) is not None
    before = svc.gated_dispatches
    # mixed grid: one session slot + one sessionless slot -> full sweep
    a = DetectionRequest(uid=200, frame=_frame(120, 160, seed=0),
                         session_id="ego")
    b = DetectionRequest(uid=201, frame=_frame(120, 160, seed=1))
    svc.submit(a)
    svc.submit(b)
    svc.run()
    assert a.ok and b.ok
    assert svc.gated_dispatches == before
    svc.close()


def test_union_gate_engages_on_covered_multisession_grid():
    clock = VirtualClock()
    svc = make_svc(clock=clock, batch_size=2)
    for t in range(6):
        for s, base in (("a", 0), ("b", 0)):
            svc.submit(DetectionRequest(
                uid=t * 10 + base + (0 if s == "a" else 1),
                frame=_frame(120, 160, seed=0), session_id=s))
        svc.run()
        clock.advance(0.01)
    assert svc.gated_dispatches > 0
    svc.close()


# --- coast starvation fix: warm-start + downshift persistence (PR 7) --------


def test_warm_start_coastable_fallback_semantics():
    """``coastable_tracks`` falls back to confirmed-but-young tracks only
    for a session that has EVER been grounded ``warm_frames`` times; the
    strict per-track bar still wins whenever it is met."""
    cfg = TrackerConfig()
    peaks = np.array([[40.0, 0.3]], np.float32)
    # cold tracker: confirmed but young track, no grounding history
    cold = LaneTracker(cfg)
    for _ in range(cfg.confirm_hits + 1):
        cold.step(peaks)
    assert cold.grounded_frames < cfg.warm_frames
    young = cold._tracks[0]
    assert young.confirmed and young.hits < cfg.coast_hits
    assert cold.coastable_tracks(1) == []            # starved, correctly
    # warm tracker: same young track state, but the SESSION is grounded
    warm = LaneTracker(cfg)
    for _ in range(cfg.warm_frames + 1):   # birth frame doesn't ground
        warm.step(peaks)
    assert warm.grounded_frames >= cfg.warm_frames
    warm._tracks[0].hits = cfg.coast_hits - 1        # re-born young track
    assert warm.coastable_tracks(1) != []            # warm start engages
    # strict bar preferred when any track meets it
    warm._tracks[0].hits = cfg.coast_hits
    assert [t.hits for t in warm.coastable_tracks(1)] == [cfg.coast_hits]


def test_tracker_step_scale_widens_rho_gate():
    """A downshifted frame's peaks carry ~factor x the rho quantization;
    ``step(scale=factor)`` widens the match gate so the track stays
    grounded instead of forking a twin."""
    cfg = TrackerConfig()
    tr = LaneTracker(cfg)
    peaks = np.array([[40.0, 0.3]], np.float32)
    for _ in range(3):
        tr.step(peaks)
    off = np.array([[40.0 + cfg.gate_rho * 1.5, 0.3]], np.float32)
    twin = LaneTracker(cfg)
    for _ in range(3):
        twin.step(peaks)
    tr.step(off)                     # native scale: outside the gate
    twin.step(off, scale=2.0)        # downshifted: gate widened 2x
    assert len(tr._tracks) == 2      # forked a twin track
    assert len(twin._tracks) == 1    # stayed grounded
    assert twin._tracks[0].hits == 4


def test_downshifted_stream_still_earns_coast():
    """The starvation fix end-to-end: a session served ONLY downshifted
    frames still accrues warm-start grounding, so a blackout frame gets a
    coast answer instead of a refusal."""
    clock = VirtualClock()
    svc = make_svc(clock=clock, validate_frames=True)
    cfg = svc.tracker_cfg
    for t in range(cfg.warm_frames + 2):
        req = DetectionRequest(uid=t, frame=_frame(120, 160, seed=0),
                               session_id="ego")
        svc.submit(req, force_bucket=(96, 128))
        svc.run()
        clock.advance(0.01)
        assert req.status is RequestStatus.DEGRADED_DOWNSHIFT
    tracker = svc.sessions["ego"]
    assert tracker.grounded_frames >= cfg.warm_frames
    assert tracker.coastable_tracks(1) != []
    bad = DetectionRequest(uid=99,
                           frame=np.full((120, 160), np.nan, np.float32),
                           session_id="ego")
    svc.submit(bad)
    svc.run()
    assert bad.status is RequestStatus.DEGRADED_COAST
    assert svc.slo["ego"].served_coast == 1
    svc.close()


# --- pre-downshift at admission (PR 7) --------------------------------------


def test_pre_downshift_engages_at_admission():
    """When the native bucket's measured backlog already makes the
    deadline infeasible at SUBMIT time, rung 1 fires immediately —
    the request never burns slack queueing at the doomed bucket."""
    clock = VirtualClock()
    svc = make_svc(clock=clock)
    _ground_estimate(svc, clock, (120, 160), 0.30, uid0=900)
    _ground_estimate(svc, clock, (96, 128), 0.01, uid0=910)
    # a wave ahead of us at the big bucket: deadline 0.15 < est 0.30
    blocker = DetectionRequest(uid=0, frame=_frame(120, 160, seed=0))
    svc.submit(blocker)
    req = DetectionRequest(uid=1, frame=_frame(120, 160, seed=1),
                           deadline_s=0.15)
    svc.submit(req)
    # downgraded at admission, before any scheduler step ran
    assert svc.pre_downshifted == 1
    assert req.bucket == (96, 128) and req.downshift == 2
    svc.run()
    assert req.status is RequestStatus.DEGRADED_DOWNSHIFT
    assert req.finished_at <= req.deadline_at
    svc.close()


def test_pre_downshift_skipped_when_feasible():
    clock = VirtualClock()
    svc = make_svc(clock=clock)
    _ground_estimate(svc, clock, (120, 160), 0.01, uid0=900)
    req = DetectionRequest(uid=0, frame=_frame(120, 160, seed=0),
                           deadline_s=1.0)
    svc.submit(req)
    assert svc.pre_downshifted == 0
    assert req.bucket == (120, 160)
    svc.run()
    assert req.ok
    svc.close()
