"""What the served path records about itself: request stamps, the vote
slot counters, and the ``service.*`` host spans a profiler trace holds.

Stamps: every frame answered in full went due <= sent <= submitted <=
admitted <= dispatched <= finished, and the three waits between them add
up to its latency.  Counters: ``edge_pixels`` is the Canny edge count the
tier choice read (capped at the batch's tier), ``vote_slots`` the tier
times the batch bucket.  Spans: each step that does work opens one, an
empty poll opens none.
"""

import glob

import jax
import numpy as np
import pytest

from repro.core import ControlConfig, HoughConfig, PipelineConfig
from repro.core.hough import max_edge_tiers, tier_for, tier_index
from repro.data import make_scenario, standard_drive_cycle
from repro.serve.detection import DetectionRequest, DetectionService

pytestmark = pytest.mark.serve

HW = (120, 160)
AUTO = PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto"))


def _tracked_service(**kw):
    return DetectionService(AUTO, buckets=(HW,), steering=ControlConfig(),
                            **kw)


def _drive(svc, n=12, streams=2):
    """``streams`` tracked sessions, frames sent in turn, stepped between
    sends, then served to the end."""
    cycles = [standard_drive_cycle("straight", n, *HW, seed=s).images()
              for s in range(streams)]
    reqs = []
    for k in range(n):
        for s, imgs in enumerate(cycles):
            r = DetectionRequest(uid=len(reqs), frame=imgs[k],
                                 deadline_s=5.0, session_id=f"cam{s}")
            svc.submit(r)
            reqs.append(r)
            svc.step()
    svc.run()
    return reqs


def test_stamps_are_ordered_and_add_up_to_the_latency():
    with _tracked_service() as svc:
        reqs = _drive(svc)
    done = [r for r in reqs if r.ok]
    assert len(done) == len(reqs)
    for r in done:
        assert (0.0 < r.submitted_at <= r.admitted_at <= r.dispatched_at
                <= r.finished_at), r
        parts = ((r.admitted_at - r.submitted_at)
                 + (r.dispatched_at - r.admitted_at)
                 + (r.finished_at - r.dispatched_at))
        assert parts == pytest.approx(r.finished_at - r.submitted_at,
                                      abs=1e-12)
    # a grid waits for its batch: some frame waited for others to arrive
    assert max(r.dispatched_at - r.admitted_at for r in done) > 0.0


def _frames():
    """Scenes with few edges, noise with more than the largest tier."""
    rng = np.random.default_rng(3)
    scenes = [make_scenario(name, *HW, seed=1).image
              for name in ("straight", "rain", "empty", "night", "glare")]
    noise = [rng.integers(0, 256, HW).astype(np.uint8) for _ in range(3)]
    return scenes[:2] + noise[:1] + scenes[2:4] + noise[1:] + scenes[4:]


def test_vote_slot_counters_read_the_edges_and_the_tier():
    """Staged path (no gate): one dispatch per pair of frames, in order."""
    frames = _frames()
    svc = DetectionService(AUTO, buckets=(HW,), batch_size=2,
                           gate_band=None, prefetch=False)
    reqs = [DetectionRequest(uid=i, frame=f) for i, f in enumerate(frames)]
    for r in reqs:
        svc.submit(r)
    svc.run()
    assert all(r.ok for r in reqs) and svc.dispatches == len(frames) // 2
    thr = AUTO.hough.edge_threshold
    counts = [int((np.asarray(r.result.edges) >= thr).sum()) for r in reqs]
    tiers = max_edge_tiers(*HW)
    assert max(counts) > tiers[-1] and min(counts) < tiers[0]
    want_pixels = want_slots = 0
    for a, b in zip(counts[::2], counts[1::2]):
        # the rule in hough_transform_tiered: the smallest tier holding
        # the batch's densest frame, capped at the last
        tier = next((t for t in tiers if max(a, b) <= t), tiers[-1])
        want_pixels += min(a, tier) + min(b, tier)
        want_slots += tier * 2
    assert svc.edge_pixels == want_pixels
    assert svc.vote_slots == want_slots


@pytest.mark.parametrize("worst", [0, 1, 511, 512, 513, 1024, 1025, 1200,
                                   1201, 10**6])
def test_host_tier_rule_is_the_device_rule(worst):
    tiers = max_edge_tiers(*HW)
    counts = jax.numpy.asarray([0, worst, 3], jax.numpy.int32)
    assert tier_for(worst, tiers) == tiers[int(tier_index(counts, tiers))]


def _service_spans(pd) -> list:
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("service.")]


def _trace(fn, tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    return ProfileData.from_file(path[-1])


def _traced(fn, tmp_path):
    return _service_spans(_trace(fn, tmp_path))


def test_spans_name_each_step_that_works_and_no_empty_poll(tmp_path):
    svc = _tracked_service()
    svc.warm_up()
    reqs = []

    def traffic():
        reqs.extend(_drive(svc, n=4))

    spans = _traced(traffic, tmp_path / "work")
    names = {n for n, _ in spans}
    assert names >= {"service.admit", "service.stage", "service.stage_wait",
                     "service.plan", "service.put", "service.launch",
                     "service.complete", "service.block", "service.fetch",
                     "service.split",
                     "service.track", "service.control", "service.drain"}
    uids = {r.uid for r in reqs}
    split = [a["uid"] for n, a in spans if n == "service.split"]
    assert sorted(split) == sorted(uids)
    staged = {a["uid"] for n, a in spans if n == "service.stage"}
    assert staged == uids
    launched = [a["dispatch"] for n, a in spans if n == "service.launch"]
    assert launched == list(range(svc.dispatches - len(launched),
                                  svc.dispatches))

    # nothing queued, slotted or in flight: a step does no work
    assert _traced(lambda: [svc.step() for _ in range(20)],
                   tmp_path / "idle") == []
    svc.close()



def _timed_spans(pd, name) -> list:
    return [(dict(ev.stats), ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_one_fetch_per_retired_batch_inside_its_completion(tmp_path):
    svc = _tracked_service()
    svc.warm_up()
    # one frame in a grid of four: the step admits it and retires nothing
    first = DetectionRequest(uid=100,
                             frame=make_scenario("straight", *HW).image)
    admit = _traced(lambda: (svc.submit(first), svc.step()),
                    tmp_path / "admit")
    assert "service.admit" in {n for n, _ in admit}
    assert not {"service.complete", "service.fetch"} & {n for n, _ in admit}
    assert svc.dispatches == 0

    pd = _trace(lambda: _drive(svc, n=4), tmp_path / "work")
    assert first.ok
    fetches = _timed_spans(pd, "service.fetch")
    completes = {a["dispatch"]: (t0, t1)
                 for a, t0, t1 in _timed_spans(pd, "service.complete")}
    # every dispatch retired inside the window, each with one fetch
    assert sorted(a["dispatch"] for a, _, _ in fetches) == list(
        range(svc.dispatches))
    assert sorted(completes) == list(range(svc.dispatches))
    for a, t0, t1 in fetches:
        c0, c1 = completes[a["dispatch"]]
        assert c0 <= t0 <= t1 <= c1
    svc.close()
