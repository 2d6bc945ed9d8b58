"""Retiring a batch runs no device program.

A finished batch crosses to the host in one fetch, and each request's
answer is a numpy slice of that copy: after ``warm_up`` no program
compiles while the service answers full-sweep, gated, fused and
downshifted batches, and every answer is bit-equal to its slot of the
batch's own device result, cropped (or mapped back to native
coordinates when downshifted).
"""

import jax
import numpy as np
import pytest
from jax._src import dispatch

from repro.core import HoughConfig, PipelineConfig
from repro.core.plan import DetectionResult
from repro.data import make_drive_cycle, make_scenario
from repro.serve.detection import (
    DetectionRequest, DetectionService, RequestStatus, VirtualClock,
    upscale_result,
)

pytestmark = pytest.mark.serve

# Shapes no other test serves, so a per-request slicing program would
# have to compile here rather than come from another test's jit cache.
NATIVE = (112, 144)
SMALL = (56, 72)
FRAME = (100, 136)      # pads into NATIVE, crops back out of it
FIELDS = ("lines", "valid", "peaks", "edges")


def _compiles():
    """A live count of backend compiles, and the callback to unregister."""
    built = []

    def on_duration(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            built.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return built, on_duration


def _serve(svc):
    """Two tracked sessions (full sweeps until their trackers confirm,
    then gated and fused batches), then one sessionless frame whose
    deadline only the small bucket can meet."""
    cycles = [make_drive_cycle("straight", 10, *FRAME, seed=s)
              for s in range(2)]
    reqs = []
    for frames in zip(*(c.frames for c in cycles)):
        for s, fr in enumerate(frames):
            r = DetectionRequest(uid=len(reqs), frame=fr.scene.image,
                                 session_id=f"cam{s}")
            svc.submit(r)
            reqs.append(r)
        svc.run()
        svc.clock.advance(0.01)
    # a measured 0.2 s at the native bucket busts a 0.05 s deadline there
    grid = svc.grids[NATIVE]
    grid.est_s, grid.est_measured = 0.2, True
    late = DetectionRequest(
        uid=len(reqs), deadline_s=0.05,
        frame=make_scenario("straight", *FRAME, seed=5).image)
    svc.submit(late)
    reqs.append(late)
    svc.run()
    return reqs


def test_retire_runs_no_device_program_and_answers_bit_equal():
    svc = DetectionService(
        PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto")),
        buckets=(SMALL, NATIVE), batch_size=2, prefetch=False,
        clock=VirtualClock(), gate_band=40, fused_corridors=8,
    )
    svc.warm_up()
    retired = []
    complete = svc._complete

    def spy(grid, **kw):
        if grid.in_flight is not None:
            retired.append(grid.in_flight[:2])
        complete(grid, **kw)

    svc._complete = spy
    built, listener = _compiles()
    try:
        reqs = _serve(svc)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    svc.close()

    assert built == []
    assert svc.gated_dispatches > 0 and svc.fused_dispatches > 0
    assert svc.gated_dispatches < svc.dispatches        # and full sweeps
    assert reqs[-1].status is RequestStatus.DEGRADED_DOWNSHIFT
    assert all(r.ok for r in reqs[:-1])

    answered = 0
    for slots, res in retired:
        batch = {f: np.asarray(getattr(res, f)) for f in FIELDS}
        for i, req in enumerate(slots):
            if req is None:
                continue
            H, W = req.frame.shape[:2]
            per = [batch[f][i] for f in FIELDS]
            if req.downshift > 1:
                dh, dw = req._ds_shape
                per[3] = per[3][:dh, :dw]
                want = upscale_result(DetectionResult(*per, None),
                                      req.downshift, H, W)
            else:
                per[3] = per[3][:H, :W]
                want = DetectionResult(*per, None)
            for f in FIELDS:
                got = getattr(req.result, f)
                assert isinstance(got, np.ndarray), (req.uid, f, type(got))
                assert got.dtype == getattr(want, f).dtype, (req.uid, f)
                np.testing.assert_array_equal(got, getattr(want, f))
            answered += 1
    assert answered == len(reqs)
