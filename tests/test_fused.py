"""Fused canny -> compact -> vote hot path (PR 8).

Layers under test, bottom-up:

  * kernel parity — kernel A (``ops.fused_weights``: xla oracle,
    interpret Pallas body) then ``ops.compact_raster``, against the staged
    ``compact_edges`` construction, bit-for-bit;
  * ``compact_raster`` — the index-scatter compaction against the generic
    row-scatter ``compact_edges`` on the same weights;
  * corridor filtering — ``corridor_keep`` geometry, the filtered vote,
    and the all-pass ``full_corridors`` identity;
  * plan math — ``fused_hough`` / ``fused_hough_tiered`` bit-exact with
    the staged transforms at full coverage (single frame, batch, gated
    band, overflow of the cap tier);
  * tracker corridors — health rules (cold start, rescan, coasting,
    overflow) and window geometry;
  * pipeline/service — the fused plan engages in steady state and the
    answers match the staged configuration exactly on a clean cycle;
  * quantized tiers — ``CannyConfig.grad_dtype`` wiring sanity.

Deterministic seeded loops throughout (no hypothesis on this host).
"""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CannyConfig, HoughConfig, PipelineConfig, canny, hough_transform,
    hough_transform_tiered,
)
from repro.core.hough import (
    CORRIDOR_INF, full_corridors, fused_hough, fused_hough_tiered,
)
from repro.core.tracking import LaneTracker, TrackerConfig, TrackingPipeline
from repro.data import make_drive_cycle, synthetic_road
from repro.kernels import ops, ref
from repro.kernels.hough_vote import compact_edges

pytestmark = pytest.mark.fused

CANNY = CannyConfig()


def _img(h=120, w=160, seed=0, noise=4.0):
    return jnp.asarray(
        np.asarray(synthetic_road(h, w, seed=seed, noise=noise).image,
                   np.float32)
    )


def _staged_compact(img, max_edges, corridors=None):
    """The staged construction of the fused output: canny -> weights ->
    (optional corridor mask) -> generic row-scatter compaction."""
    edges = canny(img, dataclasses.replace(CANNY, impl="xla"))
    H, W = edges.shape[-2:]
    jj, ii = jnp.meshgrid(jnp.arange(W), jnp.arange(H))
    xy = jnp.stack(
        [jj.ravel(), ii.ravel(), jnp.ones(H * W, jnp.int32)], axis=1
    ).astype(jnp.float32)
    flat = edges.reshape(edges.shape[:-2] + (H * W,))
    w = (flat >= 250.0).astype(jnp.float32)
    if corridors is not None:
        w = w * ref.corridor_keep(xy, corridors).astype(jnp.float32)
    return compact_edges(xy, w, max_edges=max_edges)


def _fused_detect(img, corridors, max_edges, impl):
    """The fused path's front: kernel A's weights, raster-compacted."""
    w = ops.fused_weights(img, corridors, cfg=CANNY, edge_threshold=250.0,
                          impl=impl)
    return ops.compact_raster(w, width=img.shape[-1], max_edges=max_edges)


# --- kernel parity ----------------------------------------------------------


def test_fused_detect_matches_staged_compaction():
    for seed in range(4):
        img = _img(seed=seed)
        got = _fused_detect(img, None, 256, "xla")
        want = _staged_compact(img, 256)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


def test_fused_detect_batched_and_overflow():
    imgs = jnp.stack([_img(seed=s) for s in range(3)])
    for max_edges in (16, 256):  # 16 overflows: same trailing-edge drop
        got = _fused_detect(imgs, None, max_edges, "xla")
        want = _staged_compact(imgs, max_edges)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))


def test_fused_detect_interpret_matches_oracle():
    img = _img(96, 128, seed=2)
    cors = jnp.asarray(np.array([[1.0, 0.0, 30.0, 100.0]], np.float32))
    for corridors in (None, cors):
        a = _fused_detect(img, corridors, 128, "interpret")
        b = _fused_detect(img, corridors, 128, "xla")
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_compact_raster_matches_compact_edges(rng):
    """The index-scatter compaction is bit-identical to the generic
    row-scatter on raster-layout weights — sparse, dense, empty, batched,
    and overflowing."""
    H, W = 24, 32
    jj, ii = jnp.meshgrid(jnp.arange(W), jnp.arange(H))
    xy = jnp.stack(
        [jj.ravel(), ii.ravel(), jnp.ones(H * W, jnp.int32)], axis=1
    ).astype(jnp.float32)
    for density in (0.0, 0.02, 0.3, 1.0):
        w = (rng.random((H * W,)) < density).astype(np.float32)
        for max_edges in (8, 64, 1024):
            a = ops.compact_raster(jnp.asarray(w), width=W,
                                   max_edges=max_edges)
            b = compact_edges(xy, jnp.asarray(w), max_edges=max_edges)
            np.testing.assert_array_equal(np.asarray(a[0]),
                                          np.asarray(b[0]))
            np.testing.assert_array_equal(np.asarray(a[1]),
                                          np.asarray(b[1]))
    wb = (rng.random((3, H * W)) < 0.1).astype(np.float32)
    a = ops.compact_raster(jnp.asarray(wb), width=W, max_edges=32)
    b = compact_edges(xy, jnp.asarray(wb), max_edges=32)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


# --- corridor geometry ------------------------------------------------------


def test_corridor_keep_geometry():
    """A horizontal corridor (theta=0 normal) keeps exactly the x-window;
    any-corridor OR and padding duplication are idempotent."""
    xy = jnp.asarray(
        np.array([[0.0, 5.0], [10.0, 5.0], [20.0, 5.0], [30.0, 5.0]],
                 np.float32)
    )
    cor = jnp.asarray(np.array([[1.0, 0.0, 5.0, 15.0]], np.float32))
    keep = np.asarray(ref.corridor_keep(xy, cor))
    assert keep.tolist() == [False, True, False, False]
    padded = jnp.concatenate([cor, cor, cor], axis=0)
    np.testing.assert_array_equal(
        np.asarray(ref.corridor_keep(xy, padded)), keep
    )
    both = jnp.asarray(np.array(
        [[1.0, 0.0, 5.0, 15.0], [1.0, 0.0, 25.0, 35.0]], np.float32
    ))
    assert np.asarray(ref.corridor_keep(xy, both)).tolist() == [
        False, True, False, True
    ]


def test_full_corridors_pass_everything():
    cors = full_corridors(3)
    assert cors.shape == (3, 4)
    assert (cors[:, 2] == -CORRIDOR_INF).all()
    assert (cors[:, 3] == CORRIDOR_INF).all()
    xy = jnp.asarray(np.array([[0.0, 0.0], [1000.0, 1000.0]], np.float32))
    assert np.asarray(ref.corridor_keep(xy, jnp.asarray(cors))).all()


def test_corridor_keep_exact_at_window_edges(rng):
    """With snapped normals the rho test is exact: it matches a float64
    evaluation at every 480x640 pixel, windows whose edges sit exactly on
    some pixel's rho included (the closed window keeps that pixel)."""
    H, W = 480, 640
    jj, ii = np.meshgrid(np.arange(W), np.arange(H))
    xy = np.stack([jj.ravel(), ii.ravel()], axis=1).astype(np.float32)
    theta = rng.uniform(0.0, math.pi, 4)
    cor = np.zeros((4, 4), np.float32)
    cor[:, 0], cor[:, 1] = np.cos(theta), np.sin(theta)
    normals = np.asarray(ref.snap_corridors(jnp.asarray(cor)))[:, :2]
    rho = xy.astype(np.float64) @ normals.astype(np.float64).T  # (P, 4)
    ends = rho[rng.integers(0, H * W, (2, 4)), np.arange(4)]
    cor[:, 2], cor[:, 3] = ends.min(axis=0), ends.max(axis=0)
    want = ((rho >= cor[:, 2]) & (rho <= cor[:, 3])).any(axis=1)
    got = np.asarray(ref.corridor_keep(jnp.asarray(xy), jnp.asarray(cor)))
    np.testing.assert_array_equal(got, want)
    assert want.any() and not want.all()


def test_corridor_filter_drops_off_corridor_votes():
    """With a corridor around only one of two planted lanes, the fused
    votes along the excluded lane collapse while the included lane's
    column is untouched."""
    h, w = 120, 160
    scene = synthetic_road(h, w, seed=0)
    img = jnp.asarray(np.asarray(scene.image, np.float32))
    (rho0, th0), (rho1, th1) = [
        tuple(map(float, p)) for p in scene.lines_rho_theta
    ]
    cfg = HoughConfig(compact=True, max_edges=512, corridors=2, impl="xla")
    only0 = jnp.asarray(np.array([
        [math.cos(th0), math.sin(th0), rho0 - 12.0, rho0 + 12.0],
    ] * 2, np.float32))
    votes = np.asarray(fused_hough(img, CANNY, cfg, corridors=only0))
    staged = np.asarray(hough_transform(
        canny(img, CANNY),
        HoughConfig(compact=True, max_edges=512, impl="xla"),
    ))

    def peak_height(v, rho, th):
        n_rho, n_theta = v.shape
        tb = int(round(th / math.pi * n_theta)) % n_theta
        rb = int(rho + n_rho // 2)  # rho_res=1: bin = rho + rho_max
        lo_r, hi_r = max(rb - 4, 0), min(rb + 5, n_rho)
        lo_t, hi_t = max(tb - 4, 0), min(tb + 5, n_theta)
        return v[lo_r:hi_r, lo_t:hi_t].max()

    assert peak_height(votes, rho0, th0) == peak_height(staged, rho0, th0)
    assert peak_height(votes, rho1, th1) < 0.5 * peak_height(
        staged, rho1, th1
    )


# --- plan math: bit-exactness at full coverage ------------------------------


def test_fused_hough_bit_exact_with_staged():
    cfg = HoughConfig(compact=True, max_edges=512, impl="xla")
    for seed in range(3):
        img = _img(seed=seed)
        fused = fused_hough(img, CANNY, cfg)
        staged = hough_transform(canny(img, CANNY), cfg)
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(staged))


def test_fused_tiered_bit_exact_full_corridors():
    """Exact-count tiering (host path) against the staged tiered dispatch
    — single frame, batch, and gated band, under all-pass corridors."""
    acfg = HoughConfig(compact=True, max_edges="auto", impl="xla",
                       corridors=4)
    scfg = HoughConfig(compact=True, max_edges="auto", impl="xla")
    cors = jnp.asarray(full_corridors(4))
    img = _img(seed=1)
    imgs = jnp.stack([_img(seed=s) for s in range(3)])
    for x in (img, imgs):
        fused = fused_hough_tiered(x, CANNY, acfg, corridors=cors)
        staged = hough_transform_tiered(canny(x, CANNY), scfg)
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(staged))
    tb = jnp.asarray((np.arange(40) + 50).astype(np.int32))
    bf = dataclasses.replace(acfg, theta_band=40)
    bs = dataclasses.replace(scfg, theta_band=40)
    fused = fused_hough_tiered(img, CANNY, bf, theta_bins=tb,
                               corridors=cors)
    staged = hough_transform_tiered(canny(img, CANNY), bs, theta_bins=tb)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(staged))


def test_fused_tiered_cap_overflow_matches_staged():
    """When the edge count exceeds the cap tier both dispatches drop the
    same trailing edges — overflow stays bit-exact, not merely close."""
    img = _img(seed=3)
    tiers = (16, 32)  # tiny cap: guaranteed overflow on a real frame
    acfg = HoughConfig(compact=True, max_edges="auto", impl="xla",
                       corridors=2)
    scfg = HoughConfig(compact=True, max_edges="auto", impl="xla")
    fused = fused_hough_tiered(img, CANNY, acfg, tiers,
                               corridors=jnp.asarray(full_corridors(2)))
    staged = hough_transform_tiered(canny(img, CANNY), scfg, tiers)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(staged))


def test_fused_hough_rejects_auto_and_mismatched_corridors():
    img = _img()
    with pytest.raises(ValueError, match="auto"):
        fused_hough(img, CANNY,
                    HoughConfig(compact=True, max_edges="auto"))
    cfg = HoughConfig(compact=True, max_edges=256, corridors=2,
                      impl="xla")
    with pytest.raises(ValueError, match="corridors"):
        fused_hough(img, CANNY, cfg)  # config says 2, argument missing
    with pytest.raises(ValueError, match="corridors"):
        fused_hough(img, CANNY, cfg,
                    corridors=jnp.asarray(full_corridors(3)))  # wrong C


# --- tracker corridors ------------------------------------------------------


def _warm_tracker(n=6, h=120, w=160):
    pipe = TrackingPipeline(
        PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto")),
        height=h, width=w, theta_band=40,
    )
    frame = synthetic_road(h, w, seed=0).image
    for _ in range(n):
        pipe.process(frame)
    return pipe.tracker


def test_tracker_corridor_health_rules():
    cfg = TrackerConfig()
    cold = LaneTracker(cfg)
    assert cold.corridors() is None  # cold start: no confirmed tracks

    tr = _warm_tracker()
    cors = tr.corridors()
    assert cors is not None and cors.shape[1] == 4
    n_live = cors.shape[0]

    # padding repeats the first row up to the requested budget
    padded = tr.corridors(8)
    assert padded.shape == (8, 4)
    np.testing.assert_array_equal(padded[:n_live], cors)
    for k in range(n_live, 8):
        np.testing.assert_array_equal(padded[k], cors[0])

    # overflow of the budget refuses (fall back to the staged sweep)
    assert tr.corridors(max(n_live - 1, 0)) is None

    # a coasting confirmed track poisons the set: miss a frame
    tr.step(np.zeros((0, 2), np.float32), np.zeros((0,), bool))
    assert tr.corridors() is None


def test_tracker_corridor_windows_cover_prediction():
    tr = _warm_tracker()
    cors = tr.corridors()
    half = TrackerConfig().corridor_half_px
    for t, row in zip(tr.tracks, cors):  # corridors cover every live track
        rho_p = t.rho + t.drho
        th_p = t.theta + t.dtheta
        assert row[0] == pytest.approx(math.cos(th_p), abs=1e-6)
        assert row[1] == pytest.approx(math.sin(th_p), abs=1e-6)
        assert row[2] == pytest.approx(rho_p - half, abs=1e-4)
        assert row[3] == pytest.approx(rho_p + half, abs=1e-4)


# --- pipeline + service engagement ------------------------------------------


def test_pipeline_fused_engages_and_matches_gated():
    cfg = PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto"))
    cyc = make_drive_cycle("straight", 12, 120, 160, seed=0)
    fused_pipe = TrackingPipeline(cfg, height=120, width=160,
                                  theta_band=40, fused_corridors=8)
    plain_pipe = TrackingPipeline(cfg, height=120, width=160,
                                  theta_band=40)
    for fr in cyc.frames:
        a = fused_pipe.process(fr.scene.image)
        b = plain_pipe.process(fr.scene.image)
        np.testing.assert_array_equal(np.asarray(a.result.peaks),
                                      np.asarray(b.result.peaks))
        np.testing.assert_array_equal(np.asarray(a.result.valid),
                                      np.asarray(b.result.valid))
    assert fused_pipe.fused_frames > 0
    assert fused_pipe.gated_frames == plain_pipe.gated_frames


def test_pipeline_rejects_fused_config_knobs():
    cfg = PipelineConfig(hough=HoughConfig(compact=True, corridors=4))
    with pytest.raises(ValueError, match="fused_corridors"):
        TrackingPipeline(cfg, theta_band=40)
    with pytest.raises(ValueError, match="theta_band"):
        TrackingPipeline(
            PipelineConfig(hough=HoughConfig(compact=True)),
            theta_band=None, fused_corridors=4,
        )


def test_service_fused_engages_and_matches():
    from repro.serve.detection import (
        DetectionRequest, DetectionService, VirtualClock,
    )

    def run(fused_corridors):
        svc = DetectionService(
            PipelineConfig(
                hough=HoughConfig(compact=True, max_edges="auto")
            ),
            buckets=((120, 160),), batch_size=1, prefetch=False,
            clock=VirtualClock(), gate_band=40,
            fused_corridors=fused_corridors,
        )
        cyc = make_drive_cycle("straight", 10, 120, 160, seed=0)
        out = []
        for fr in cyc.frames:
            req = DetectionRequest(uid=fr.t, frame=fr.scene.image,
                                   session_id="ego")
            svc.submit(req)
            svc.run()
            svc.clock.advance(0.01)
            out.append(req)
        counts = (svc.gated_dispatches, svc.fused_dispatches)
        svc.close()
        return out, counts

    got, (gated_f, fused_f) = run(8)
    ref_, (gated_p, fused_p) = run(None)
    assert fused_f > 0 and fused_p == 0
    for g, r in zip(got, ref_):
        assert g.ok and r.ok
        np.testing.assert_array_equal(np.asarray(g.result.peaks),
                                      np.asarray(r.result.peaks))
        np.testing.assert_array_equal(np.asarray(g.result.valid),
                                      np.asarray(r.result.valid))


def test_service_ships_gate_and_corridors_before_guarded_dispatch(
        monkeypatch):
    """Warm gated/fused dispatches run under transfer_guard("disallow").
    On the CPU backend an implicit numpy->device copy is not guarded, so
    pin the contract directly: every gate and corridor array reaching
    ``DetectionPlan.run`` is already a jax.Array on the service's device
    (a TPU backend raises on the implicit copy)."""
    import jax

    from repro.core.plan import DetectionPlan
    from repro.serve.detection import (
        DetectionRequest, DetectionService, VirtualClock,
    )

    seen = []
    real_run = DetectionPlan.run

    def spy(self, images, theta_bins=None, corridors=None):
        seen.append((theta_bins, corridors))
        return real_run(self, images, theta_bins, corridors)

    monkeypatch.setattr(DetectionPlan, "run", spy)
    svc = DetectionService(
        PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto")),
        buckets=((120, 160),), batch_size=1, prefetch=False,
        clock=VirtualClock(), gate_band=40, fused_corridors=8,
    )
    device = jax.devices()[0]
    for fr in make_drive_cycle("straight", 10, 120, 160, seed=0).frames:
        svc.submit(DetectionRequest(uid=fr.t, frame=fr.scene.image,
                                    session_id="ego"))
        svc.run()
        svc.clock.advance(0.01)
    assert svc.gated_dispatches > 0 and svc.fused_dispatches > 0
    svc.close()
    operands = [a for pair in seen for a in pair if a is not None]
    assert operands
    for a in operands:
        assert isinstance(a, jax.Array), type(a)
        assert a.devices() == {device}


def test_service_warm_up_covers_every_dispatch():
    """After ``warm_up`` every binding a tracked session takes (full sweep,
    gated, fused) is already warm: no dispatch compiles, and the gated and
    fused ones all run under the transfer guard."""
    from repro.serve.detection import (
        DetectionRequest, DetectionService, VirtualClock,
    )

    svc = DetectionService(
        PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto")),
        buckets=((120, 160),), batch_size=1, prefetch=False,
        clock=VirtualClock(), gate_band=40, fused_corridors=4,
    )
    svc.warm_up()
    warmed = set(svc._warmed)
    assert {k[2:] for k in warmed} == {(None, False), (40, False), (40, True)}
    for fr in make_drive_cycle("straight", 10, 120, 160, seed=0).frames:
        req = DetectionRequest(uid=fr.t, frame=fr.scene.image,
                               session_id="ego")
        svc.submit(req)
        svc.run()
        assert req.ok
        svc.clock.advance(0.01)
    assert svc.gated_dispatches > 0 and svc.fused_dispatches > 0
    assert svc._warmed == warmed
    svc.close()


# --- quantized gradient tiers ----------------------------------------------


def test_grad_dtype_tiers_run_and_validate():
    img = _img(seed=0)
    base = np.asarray(canny(img, CANNY))
    for grad in ("f16", "int8"):
        out = np.asarray(
            canny(img, dataclasses.replace(CANNY, grad_dtype=grad))
        )
        assert out.shape == base.shape and out.dtype == base.dtype
        # low-precision gradients move few edge pixels on a clean scene
        assert (out != base).mean() < 0.03
    with pytest.raises(ValueError, match="integer"):
        canny(img, dataclasses.replace(
            CANNY, integer=True, grad_dtype="f16"
        ))
    with pytest.raises(ValueError, match="grad_dtype"):
        canny(img, dataclasses.replace(CANNY, grad_dtype="bf8"))
