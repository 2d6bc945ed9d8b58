"""End-to-end line detection pipeline (paper Section 4.3-4.4).

Three phases, exactly the paper's Table 1 decomposition:

  1. image load        — decode/normalize the input frame (host -> device),
  2. line detection    — Canny -> Hough -> get-coordinates (device),
  3. image generation  — render detected lines into an output frame.

Phase 3 is implemented *and elidable* (``render_output=False``), reproducing
the paper's 4.2x elision win.

Plan architecture (``core/plan.py``): a ``LineDetector`` no longer decides
anything per call.  Each ``(height, width, batch-bucket)`` workload resolves
ONCE into a frozen ``DetectionPlan`` — all ``"auto"`` knobs fixed, batch
padding bucket chosen, autotune tiers pinned — and every subsequent call
reuses the plan's compiled body.  ``max_edges="auto"`` is resolved *on the
device* (an edge-count reduction selects among a static set of compaction
tiers via ``lax.switch``), so ``detect_stream`` performs zero per-chunk
device<->host syncs: frames are staged on the host, shipped with one
explicit ``jax.device_put`` per batch, and the hot loop runs under
``jax.transfer_guard("disallow")``.  Short final batches pad to the plan's
bucket instead of recompiling.  ``benchmarks/lines_throughput.py`` measures
the batch path; ``serve/detection.py`` builds a request-level service on
the same plans.

Temporal layer (``core/tracking.py``): a camera stream carries frame-to-
frame continuity this per-frame facade ignores — ``TrackingPipeline``
wraps the same plans with a ``LaneTracker`` whose confirmed tracks gate
the next frame's Hough sweep to predicted theta windows
(``DetectionPlan.with_theta_band`` / ``run(theta_bins=...)``), falling
back to the full sweep on track loss; ``data/scenarios.py`` drive cycles
are the matching workload.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator

import jax
import numpy as np

import jax.numpy as jnp

from .canny import canny, estimate_edge_count
from .hough import hough_transform, resolved_auto_config
from .lines import get_lines
from .plan import (  # noqa: F401  (re-exported API)
    DetectionPlan, DetectionResult, LUMA_WEIGHTS, PipelineConfig, PlanCache,
    batch_bucket, load_frame,
)
from .profiling import PhaseProfiler


class LineDetector:
    """The paper's application as a composable, jittable module.

    A thin facade over ``core/plan.py``: calls look up (or build) the
    ``DetectionPlan`` for their workload shape and run it.  Detector
    instances with equal configs share compiled bodies via the jit cache.
    """

    def __init__(self, cfg: PipelineConfig = PipelineConfig()):
        self.cfg = cfg
        self._plans = PlanCache(cfg)

    # --- phase 1: image load ------------------------------------------
    @staticmethod
    def load(raw: jax.Array) -> jax.Array:
        """uint8 frame (possibly RGB) -> grayscale f32-ready device array.

        Trace-safe device twin of the host staging path ``plan.load_frame``
        (shared ``LUMA_WEIGHTS``, f32 math in the same order; XLA fusion
        may differ in the last ulp); grayscale inputs pass through at
        their own dtype (the integer pipeline keeps exact uint8 values)."""
        img = jnp.asarray(raw)
        if img.ndim == 3:  # luma conversion
            wr, wg, wb = LUMA_WEIGHTS
            img = img.astype(jnp.float32)
            img = wr * img[..., 0] + wg * img[..., 1] + wb * img[..., 2]
        return img

    # --- plan access ---------------------------------------------------
    def plan_for(self, height: int, width: int, *,
                 batch: int | None = None) -> DetectionPlan:
        """The resolve-once execution plan for a workload shape."""
        return self._plans.plan_for(height, width, batch=batch)

    # --- data-dependent config resolution ------------------------------
    def resolve_config(self, image: jax.Array | None = None
                       ) -> PipelineConfig:
        """Resolve data-dependent knobs against a concrete frame/batch.

        Legacy/introspection path: sizes the ``max_edges="auto"`` buffer
        from the downsampled gradient estimate (one host readback) and
        returns a fully pinned config.  The detect paths no longer need
        this — their plans resolve "auto" on the device (``core/plan.py``)
        — but benchmarks and the service use it to *report* the buffer a
        workload would get, and pinning a detector to the result is still
        valid (it just skips the tiered dispatch).
        """
        h = self.cfg.hough
        if h.max_edges != "auto":
            return self.cfg
        if h.compact:
            if image is None or isinstance(image, jax.core.Tracer):
                raise ValueError(
                    "max_edges='auto' needs a concrete input frame to size "
                    "the compaction buffer (it is a static shape)."
                )
            H, W = image.shape[-2:]
            n_est = estimate_edge_count(image, self.cfg.canny)
        else:  # dense path: the knob is inert, keep jit keys stable
            H = W = n_est = 0
        return dataclasses.replace(
            self.cfg, hough=resolved_auto_config(h, n_est, H, W)
        )

    # --- phase 2: line detection --------------------------------------
    def detect(self, image: jax.Array) -> DetectionResult:
        """Detect lines in one frame (H, W) — or a batch (N, H, W), which
        delegates to ``detect_batch``."""
        if image.ndim == 3:
            return self.detect_batch(image)
        H, W = image.shape[-2:]
        return self.plan_for(H, W).run(image)

    # --- batched fast path --------------------------------------------
    def detect_batch(self, images: jax.Array) -> DetectionResult:
        """Detect lines in a stack of frames (N, H, W) as ONE jitted
        program: the conv/vote kernels lower the batch as a leading grid
        axis, so every field of the result gains a leading N axis.  The
        batch pads to its plan's power-of-two bucket (frame-independent
        stages make pad rows inert) and the result is sliced back.  The
        frames may be a heterogeneous scenario mix (``data/scenarios.py``)
        — with ``max_edges="auto"`` the device-side autotune picks the
        tier that holds the densest frame.  Bit-exact with a per-frame
        ``detect`` loop (the kernels are row/frame-independent, and
        integer-valued vote sums are exact in f32 at any buffer size that
        drops no edges)."""
        assert images.ndim == 3, images.shape
        N, H, W = images.shape
        return self.plan_for(H, W, batch=batch_bucket(N)).run(images)

    def detect_stream(
        self, frames: Iterable, *, batch_size: int = 1,
    ) -> Iterator[DetectionResult]:
        """Pinned, double-buffered streaming detection over a frame iterator.

        ONE plan is built from the first frame's resolution and the
        ``batch_size`` bucket, then every chunk — including a short final
        one, which pads to the bucket instead of recompiling — reuses it.
        Chunks are staged on the host (numpy decode + stack) and shipped
        with a single explicit ``jax.device_put`` each; after the first
        (compiling) chunk the loop runs under
        ``jax.transfer_guard("disallow")``, so any per-chunk host
        round-trip — implicit transfer, estimator readback, re-resolution
        — is a hard error rather than a silent stall.  Dispatch is
        asynchronous: while the device computes batch k, the host decodes
        and stages batch k+1 (one batch in flight).  Yields one per-frame
        DetectionResult per input frame, in order.
        """
        plan: DetectionPlan | None = None
        warmed = False

        def dispatch(chunk):
            nonlocal plan, warmed
            arr = np.stack([load_frame(f) for f in chunk])
            n, H, W = arr.shape
            if plan is None:
                # same pow2 bucket as detect_batch, so a warmup batch and
                # the stream share one compiled program
                plan = self.plan_for(H, W, batch=batch_bucket(batch_size))
            if n < plan.batch:  # pad on the host: one transfer either way
                arr = np.concatenate(
                    [arr, np.zeros((plan.batch - n, H, W), arr.dtype)]
                )
            if not warmed:  # first chunk compiles: transfers constants
                warmed = True
                return plan.run(jax.device_put(arr)), n
            with jax.transfer_guard("disallow"):
                return plan.run(jax.device_put(arr)), n

        def split(res, n):
            for i in range(n):
                yield DetectionResult(
                    res.lines[i], res.valid[i], res.peaks[i],
                    res.edges[i],
                    None if res.rendered is None else res.rendered[i],
                )

        in_flight = None
        buf = []
        for frame in frames:
            buf.append(frame)
            if len(buf) == batch_size:
                res = dispatch(buf)   # async: device starts batch k+1
                buf = []
                if in_flight is not None:
                    yield from split(*in_flight)
                in_flight = res
        if buf:
            res = dispatch(buf)
            if in_flight is not None:
                yield from split(*in_flight)
            in_flight = res
        if in_flight is not None:
            yield from split(*in_flight)

    def detect_stage_profiled(
        self, image: jax.Array, repeats: int = 1
    ) -> PhaseProfiler:
        """Paper Table 3: Canny vs Hough vs get-coordinates split.

        Accepts a single frame (H, W) or a batch (N, H, W) — the batched
        split feeds the throughput benchmark's per-stage table.
        """
        prof = PhaseProfiler()
        H, W = image.shape[-2:]
        cfg = self.resolve_config(image)
        canny_j = jax.jit(lambda im: canny(im, cfg.canny))
        hough_j = jax.jit(lambda e: hough_transform(e, cfg.hough))
        lines_j = jax.jit(
            lambda v: get_lines(v, height=H, width=W, cfg=cfg.lines)
        )
        edges = canny_j(image)  # warmup chains
        votes = hough_j(edges)
        lines_j(votes)
        for _ in range(repeats):
            edges = prof.timeit("canny", canny_j, image)
            votes = prof.timeit("hough", hough_j, edges)
            prof.timeit("get_coordinates", lines_j, votes)
        return prof
