"""Behaviour tests for the paper's system: Canny -> Hough -> lines.

These tests assert the paper's *claims*, not just shapes:
  * §4.4 float->int rewrite loses no detection accuracy (int == float peaks),
  * the GEMM-form Hough equals the paper's Algorithm 2 loop,
  * the full pipeline recovers planted lines with known (rho, theta),
  * eliding output-image generation (paper Table 1/2) changes no detection.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CannyConfig, HoughConfig, LineDetector, LinesConfig, PipelineConfig,
    canny, get_lines, hough_paper_loop, hough_transform, plan_line_detection,
    quantize, dequantize, quantized_matmul,
)
from repro.core.lines import render_lines
from repro.data.images import synthetic_road


def detected_set(res, tol_rho=4.0, tol_theta_deg=3.0):
    out = []
    for (r, t), ok in zip(np.asarray(res.peaks), np.asarray(res.valid)):
        if ok:
            out.append((float(r), math.degrees(float(t))))
    return out


def recovers(planted, got, tol_rho=5.0, tol_theta=3.0):
    for rho, theta in planted:
        deg = math.degrees(theta)
        if not any(
            abs(r - rho) <= tol_rho and abs(t - deg) <= tol_theta
            for r, t in got
        ):
            return False
    return True


@pytest.fixture(scope="module")
def scene():
    return synthetic_road(120, 160, seed=3)


def test_detects_planted_lines(scene):
    det = LineDetector(PipelineConfig())
    res = det.detect(jnp.asarray(scene.image, jnp.float32))
    got = detected_set(res)
    assert recovers(scene.lines_rho_theta, got), (scene.lines_rho_theta, got)


def test_int_rewrite_detection_parity(scene):
    """Paper §4.4: integer pipeline, no accuracy loss."""
    det_f = LineDetector(PipelineConfig())
    det_i = LineDetector(PipelineConfig(canny=CannyConfig(integer=True)))
    img = jnp.asarray(scene.image, jnp.float32)
    got_f = detected_set(det_f.detect(img))
    got_i = detected_set(det_i.detect(jnp.asarray(scene.image)))
    assert recovers(scene.lines_rho_theta, got_i)
    # same peaks within a bin
    assert len(got_f) == len(got_i)
    for (rf, tf), (ri, ti) in zip(sorted(got_f), sorted(got_i)):
        assert abs(rf - ri) <= 2.0 and abs(tf - ti) <= 2.0


def test_fused_masks_detection_parity(scene):
    """Beyond-paper single-pass 7x7 fusion detects the same lines."""
    det = LineDetector(PipelineConfig(canny=CannyConfig(fused=True)))
    got = detected_set(det.detect(jnp.asarray(scene.image, jnp.float32)))
    assert recovers(scene.lines_rho_theta, got)


def test_paper_variant_runs(scene):
    det = LineDetector(PipelineConfig(canny=CannyConfig(variant="paper")))
    res = det.detect(jnp.asarray(scene.image, jnp.float32))
    assert res.edges.shape == scene.image.shape


def test_hough_gemm_equals_paper_loop(scene):
    edges = canny(jnp.asarray(scene.image, jnp.float32), CannyConfig())
    cfg = HoughConfig(n_theta=90)
    fast = hough_transform(edges, cfg)
    slow = hough_paper_loop(edges, cfg)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(slow), atol=1e-3)


def test_sum_squares_same_bits_fused_or_not(rng):
    """The Canny magnitude's ``gx*gx + gy*gy`` gives the same f32 bits
    whether or not the compiler fuses multiply-adds (jit on the CPU does,
    op-by-op eager dispatch cannot), within two roundings of the exact
    integer sum, for integer gradients up to the f32 tier's 2**18."""
    from repro.core.canny import _sum_squares

    gx, gy = rng.integers(-2**18 + 1, 2**18, (2, 200_000)).astype(np.float32)
    fused = np.asarray(jax.jit(_sum_squares)(gx, gy))
    unfused = np.asarray(_sum_squares(jnp.asarray(gx), jnp.asarray(gy)))
    np.testing.assert_array_equal(fused, unfused)
    exact = gx.astype(np.int64) ** 2 + gy.astype(np.int64) ** 2
    err = np.abs(fused.astype(np.float64) - exact)
    assert (err <= exact * 2.0**-23).all()


def test_vote_conservation(scene):
    """Every edge pixel casts exactly n_theta votes (minus out-of-range)."""
    edges = canny(jnp.asarray(scene.image, jnp.float32), CannyConfig())
    cfg = HoughConfig()
    votes = hough_transform(edges, cfg)
    n_edge = int(np.asarray(edges >= cfg.edge_threshold).sum())
    total = float(np.asarray(votes).sum())
    assert abs(total - n_edge * cfg.n_theta) <= n_edge  # floor() edge bins


def test_render_and_elide(scene):
    det_off = LineDetector(PipelineConfig(render_output=False))
    det_on = LineDetector(PipelineConfig(render_output=True))
    img = jnp.asarray(scene.image, jnp.float32)
    r_off = det_off.detect(img)
    r_on = det_on.detect(img)
    assert r_off.rendered is None
    assert r_on.rendered is not None and r_on.rendered.shape == (
        *scene.image.shape, 3)
    np.testing.assert_array_equal(np.asarray(r_on.lines),
                                  np.asarray(r_off.lines))
    # rendered image marks some pixels red
    red = np.asarray(r_on.rendered)
    assert ((red[..., 0] == 255) & (red[..., 1] == 0)).sum() > 0


def test_detect_batch_matches_loop_bit_exact():
    """The batched fast path is the same program per frame: every field of
    detect_batch((N, H, W)) equals the per-frame detect loop bit-for-bit."""
    frames = np.stack(
        [synthetic_road(96, 128, seed=s).image for s in (1, 2, 3)]
    )
    det = LineDetector(PipelineConfig(render_output=True))
    imgs = jnp.asarray(frames, jnp.float32)
    rb = det.detect_batch(imgs)
    for i in range(frames.shape[0]):
        r = det.detect(imgs[i])
        np.testing.assert_array_equal(np.asarray(rb.lines[i]),
                                      np.asarray(r.lines))
        np.testing.assert_array_equal(np.asarray(rb.valid[i]),
                                      np.asarray(r.valid))
        np.testing.assert_array_equal(np.asarray(rb.peaks[i]),
                                      np.asarray(r.peaks))
        np.testing.assert_array_equal(np.asarray(rb.edges[i]),
                                      np.asarray(r.edges))
        np.testing.assert_array_equal(np.asarray(rb.rendered[i]),
                                      np.asarray(r.rendered))


def test_detect_stream_matches_batch():
    """Double-buffered streaming yields the same per-frame results, in
    order, across batch boundaries and a short final batch."""
    frames = [synthetic_road(96, 128, seed=s).image for s in range(5)]
    det = LineDetector(PipelineConfig())
    rb = det.detect_batch(jnp.asarray(np.stack(frames), jnp.float32))
    got = list(det.detect_stream(iter(frames), batch_size=2))
    assert len(got) == 5
    for i, r in enumerate(got):
        np.testing.assert_array_equal(np.asarray(r.lines),
                                      np.asarray(rb.lines[i]))
        np.testing.assert_array_equal(np.asarray(r.valid),
                                      np.asarray(rb.valid[i]))


@pytest.mark.parametrize("max_edges", [None, "auto"])
def test_detect_stream_uneven_tail_matches_frame_loop(max_edges):
    """Batch-tail correctness: a batch size that does not divide the frame
    count (7 frames, batch 3 -> chunks 3/3/1) is bit-exact with the
    per-frame detect loop on every result field, including with the
    autotuned compaction buffer resolved per chunk."""
    frames = [synthetic_road(96, 128, seed=s).image for s in range(7)]
    det = LineDetector(PipelineConfig(
        hough=HoughConfig(compact=True, max_edges=max_edges)
    ))
    got = list(det.detect_stream(iter(frames), batch_size=3))
    assert len(got) == 7
    for f, r in zip(frames, got):
        ref = det.detect(jnp.asarray(f, jnp.float32))
        np.testing.assert_array_equal(np.asarray(r.lines),
                                      np.asarray(ref.lines))
        np.testing.assert_array_equal(np.asarray(r.valid),
                                      np.asarray(ref.valid))
        np.testing.assert_array_equal(np.asarray(r.peaks),
                                      np.asarray(ref.peaks))
        np.testing.assert_array_equal(np.asarray(r.edges),
                                      np.asarray(ref.edges))


def test_compact_hough_pipeline_bit_exact(scene):
    """Edge compaction changes the iteration space, not the votes: the
    compacted pipeline's accumulator and detections match the dense path
    exactly (vote counts are small integers in f32)."""
    img = jnp.asarray(scene.image, jnp.float32)
    edges = canny(img, CannyConfig())
    v_dense = hough_transform(edges, HoughConfig())
    v_comp = hough_transform(edges, HoughConfig(compact=True))
    np.testing.assert_array_equal(np.asarray(v_dense), np.asarray(v_comp))

    det_d = LineDetector(PipelineConfig())
    det_c = LineDetector(PipelineConfig(hough=HoughConfig(compact=True)))
    rd, rc = det_d.detect(img), det_c.detect(img)
    np.testing.assert_array_equal(np.asarray(rd.lines), np.asarray(rc.lines))
    np.testing.assert_array_equal(np.asarray(rd.valid), np.asarray(rc.valid))


def test_batched_canny_and_hough_shapes(scene):
    """(N, H, W) flows through canny/hough/get_lines with leading axes."""
    imgs = jnp.asarray(
        np.stack([scene.image, np.flipud(scene.image)]), jnp.float32
    )
    edges = canny(imgs, CannyConfig())
    assert edges.shape == imgs.shape and edges.dtype == jnp.uint8
    votes = hough_transform(edges, HoughConfig())
    assert votes.ndim == 3 and votes.shape[0] == 2
    lines, valid, peaks = get_lines(
        votes, height=imgs.shape[1], width=imgs.shape[2],
        cfg=LinesConfig(max_lines=8),
    )
    assert lines.shape == (2, 8, 4)
    assert valid.shape == (2, 8)
    assert peaks.shape == (2, 8, 2)


def test_get_lines_static_shapes():
    votes = jnp.zeros((100, 180))
    votes = votes.at[30, 45].set(99.0)
    lines, valid, peaks = get_lines(votes, height=64, width=64,
                                    cfg=LinesConfig(max_lines=8))
    assert lines.shape == (8, 4) and valid.shape == (8,)
    assert int(valid.sum()) == 1


def test_quantize_roundtrip(rng):
    x = rng.normal(size=(64, 64)).astype(np.float32)
    q = quantize(jnp.asarray(x))
    err = np.abs(np.asarray(dequantize(q)) - x).max()
    assert err <= float(np.abs(x).max()) / 127.0 + 1e-6


def test_quantized_matmul_error_bound(rng):
    x = rng.normal(size=(32, 48)).astype(np.float32)
    y = rng.normal(size=(48, 16)).astype(np.float32)
    got = np.asarray(quantized_matmul(jnp.asarray(x), jnp.asarray(y)))
    want = x @ y
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 0.05, rel


def test_offload_plan_matches_paper_partition():
    """GEMM stages -> MXU; elementwise/control stages -> VPU (paper's split)."""
    plan = plan_line_detection(720, 1280)
    units = {p.stage: p.unit for p in plan}
    assert units["canny_conv_gemm"] == "mxu"
    assert units["hough_rho_gemm"] == "mxu"
    assert units["canny_elementwise"] == "vpu"
    assert units["hough_votes"] == "vpu"
    assert units["get_coordinates"] == "vpu"
