"""Resolve-once execution plans for the line-detection stack.

"Deciding how to run" and "running" used to be interleaved: every
``LineDetector`` call re-resolved data-dependent knobs (``max_edges="auto"``
copied each batch back to the host to count gradients), and every distinct
batch shape recompiled.  This module splits them:

  * A frozen :class:`DetectionPlan` is built exactly once per
    ``(height, width, batch-bucket)`` and pins everything static — the fully
    resolved :class:`PipelineConfig`, the batch padding bucket, and (for
    ``max_edges="auto"``) the static tier set the device-side autotune
    dispatches over.  Plans are pure facts; the compiled callables they bind
    to are the module-level jitted bodies below, so two detectors with equal
    configs share one compilation.
  * Device-side autotune: the plan's ``"auto"`` body counts edge pixels on
    the device (a reduction over the Canny output) and ``lax.switch``-es
    between vote kernels compiled for a small static set of ``max_edges``
    tiers (``core.hough.max_edge_tiers``).  No per-batch host round-trip —
    ``LineDetector.detect_stream`` runs its hot loop under
    ``jax.transfer_guard("disallow")``.

``core/pipeline.py`` re-exports the config/result types and layers the
user-facing ``LineDetector`` on top; ``serve/detection.py`` builds one plan
per resolution bucket for the continuous-batching detection service.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .canny import CannyConfig, canny
from .hough import (
    HoughConfig, fused_hough, fused_hough_counted, hough_transform,
    hough_transform_counted, max_edge_tiers,
)
from .lines import LinesConfig, get_lines, render_lines


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    canny: CannyConfig = CannyConfig()
    hough: HoughConfig = HoughConfig()
    lines: LinesConfig = LinesConfig()
    render_output: bool = False   # paper's elision: off by default
    # Fused hot path (kernels/fused_detect.py): canny -> corridor filter ->
    # compact -> vote with no intermediate HBM arrays.  Requires
    # ``hough.compact=True`` (the fused kernel's output IS the compacted
    # edge list).  The ``edges`` field of the result is a zeros placeholder
    # on this path — eliding the edge map is the point of the fusion.
    # Bit-exact with the staged path at full corridor/band coverage.
    fused: bool = False


class DetectionResult(NamedTuple):
    # Per-frame shapes; every field gains a leading N axis from
    # detect_batch (detect_stream splits that axis back off).
    lines: jax.Array      # (K, 4) endpoints
    valid: jax.Array      # (K,) mask
    peaks: jax.Array      # (K, 2) (rho, theta)
    edges: jax.Array      # (H, W) uint8 Canny output
    rendered: jax.Array | None
    # () int32 edge pixels the compaction tier choice counted (after the
    # corridor filter on the fused path); tiered plans only, else None.
    # Last and defaulted, so five-field constructions still work.
    edge_count: jax.Array | None = None


# BT.601 luma weights — the single source for BOTH grayscale conversions:
# the host staging path (load_frame) and the device path
# (LineDetector.load).  Same weights, same f32 order; XLA may still fuse
# the multiply-adds, so the two can differ in the last ulp (gray inputs —
# every test/benchmark path — are untouched by either).
LUMA_WEIGHTS = (0.299, 0.587, 0.114)


def load_frame(raw) -> np.ndarray:
    """Host-side phase 1: uint8 frame (possibly RGB) -> grayscale f32.

    Pure numpy so streaming can stage whole batches on the host and ship
    them with ONE explicit ``jax.device_put`` — the pinned-transfer
    discipline ``transfer_guard("disallow")`` enforces on the hot loop.
    """
    img = np.asarray(raw)
    if img.ndim == 3:  # luma conversion
        wr, wg, wb = LUMA_WEIGHTS
        img = img.astype(np.float32)
        img = wr * img[..., 0] + wg * img[..., 1] + wb * img[..., 2]
    return np.asarray(img, np.float32)


def downsample2x(img: np.ndarray) -> np.ndarray:
    """Host-side 2x2 mean-pool of a grayscale f32 frame (edge-replicated
    to even dimensions first, so the last row/column is never dropped).

    Pure numpy on purpose: the degradation ladder downshifts frames on
    the scheduler/staging path, where everything stays host-side until
    the single ``jax.device_put`` per dispatch.  Mean pooling (not
    striding) keeps a 1-px lane stroke visible after the shift — a
    stride-2 subsample could step over the stroke entirely, which would
    turn "degraded answer" into "no answer".
    """
    img = np.asarray(img, np.float32)
    H, W = img.shape
    if H % 2:
        img = np.concatenate([img, img[-1:, :]], axis=0)
    if W % 2:
        img = np.concatenate([img, img[:, -1:]], axis=1)
    return (0.25 * (img[0::2, 0::2] + img[1::2, 0::2]
                    + img[0::2, 1::2] + img[1::2, 1::2])
            ).astype(np.float32)


def downshift_frame(raw, shape: tuple[int, int]
                    ) -> tuple[np.ndarray, int]:
    """Grayscale-load ``raw`` and halve its resolution until it fits the
    ``shape`` bucket; returns ``(image, factor)`` with ``factor`` the
    power-of-two divisor applied (1 = it already fit).

    Power-of-two factors keep the coordinate mapping exact: a native
    pixel center x maps to downshifted center ``(x - c) / factor`` with
    ``c = (factor - 1) / 2`` (the mean-pool's phase offset), so results
    computed at the low resolution scale back to native (rho, theta)
    coordinates in closed form (``serve.detection.upscale_result``).
    """
    img = load_frame(raw)
    factor = 1
    while img.shape[0] > shape[0] or img.shape[1] > shape[1]:
        img = downsample2x(img)
        factor *= 2
    return img, factor


@functools.partial(jax.jit, static_argnames=("cfg", "tiers"))
def _detect(cfg: PipelineConfig, image: jax.Array,
            theta_bins: jax.Array | None = None,
            corridors: jax.Array | None = None, *,
            tiers: tuple[int, ...] | None = None) -> DetectionResult:
    """The one jitted detection body, shared across detector instances.

    With ``tiers=None``, ``cfg`` must be fully resolved (no "auto" knobs).
    With a tier tuple — the ``max_edges="auto"`` plan path — the device
    counts the Canny edge pixels (max over a batch: the compaction buffer
    is shared) and ``lax.switch``-es the vote stage to the tier that holds
    them all; one compiled program per (shape, cfg), zero host
    round-trips; the per-frame counts come back as ``edge_count``.
    ``theta_bins`` (required iff ``cfg.hough.theta_band`` is set) carries
    the prediction gate: the vote sweeps only those theta bins
    (``core/tracking.py`` slides the gate frame to frame; the band length
    is the static part, so the program never recompiles).
    ``corridors`` (required iff ``cfg.hough.corridors`` is set — fused
    path only) is the (C, 4) rho-window set that pre-filters edge pixels.

    Every device op runs under one named scope — ``canny``, ``compact``
    (the count, the tier choice and the prefix-sum scatter), ``vote``,
    ``get_lines`` or ``render`` — which the compiled program's op
    metadata carries, so a profile can put device time down to a stage.
    """
    H, W = image.shape[-2:]
    counts = None
    if cfg.fused:
        # Fused hot path: no edge map ever materializes — kernel A emits
        # the compacted (corridor-filtered) edge list straight from the
        # frame, and the result's ``edges`` field is a zeros placeholder.
        with jax.named_scope("canny"):
            edges = jnp.zeros(image.shape, jnp.uint8)
        if tiers is None:
            votes = fused_hough(image, cfg.canny, cfg.hough, theta_bins,
                                corridors, scatter=False)
        else:
            votes, counts = fused_hough_counted(
                image, cfg.canny, cfg.hough, tiers, theta_bins, corridors,
                scatter=False)
    else:
        if corridors is not None:
            raise ValueError(
                "corridors is a fused-path argument; this plan is staged "
                "(PipelineConfig.fused=False)"
            )
        with jax.named_scope("canny"):
            edges = canny(image, cfg.canny)
        # gated frames stay in band space end to end: the vote emits the
        # (n_rho, theta_band) accumulator and get_lines searches exactly
        # those columns, so the whole post-Canny stack scales with the band
        if tiers is None:
            votes = hough_transform(edges, cfg.hough, theta_bins,
                                    scatter=False)
        else:
            votes, counts = hough_transform_counted(
                edges, cfg.hough, tiers, theta_bins, scatter=False)
    with jax.named_scope("get_lines"):
        lines, valid, peaks = get_lines(
            votes, height=H, width=W, cfg=cfg.lines, theta_bins=theta_bins
        )
    rendered = None
    if cfg.render_output:
        with jax.named_scope("render"):
            rendered = render_lines(image.astype(jnp.uint8), lines, valid)
    return DetectionResult(lines, valid, peaks, edges, rendered, counts)


def batch_bucket(n: int) -> int:
    """Round a batch size up to the next power of two.

    Drifting batch sizes (uneven stream tails, partially full service
    slots) pad to a bucket instead of recompiling at their own shape."""
    if n <= 1:
        return 1
    b = 1
    while b < n:
        b *= 2
    return b


def resolve_static(cfg: PipelineConfig, height: int, width: int
                   ) -> tuple[PipelineConfig, tuple[int, ...] | None]:
    """Resolve every shape-static knob of ``cfg`` for one resolution.

    Returns ``(resolved_cfg, tiers)``: ``tiers`` is the static
    ``max_edges`` tier set when the config asks for the device-side
    autotune (``compact=True, max_edges="auto"``), else ``None`` with any
    inert ``"auto"`` neutralized so jit cache keys stay shared.  Pure and
    idempotent — ``resolve_static(*resolve_static(cfg, h, w)[:1], h, w)``
    is a fixed point (property-tested in ``tests/test_detection_service``).
    """
    h = cfg.hough
    if h.max_edges != "auto":
        return cfg, None
    if not h.compact:  # knob inert on the dense path
        return dataclasses.replace(
            cfg, hough=dataclasses.replace(h, max_edges=None)
        ), None
    return cfg, max_edge_tiers(height, width)


@dataclasses.dataclass(frozen=True)
class DetectionPlan:
    """A frozen "how to run" record for one ``(H, W, batch)`` workload.

    Everything data-independent is decided at build time: the resolved
    config, the batch padding bucket, and the autotune tier set.  ``run``
    only pads, dispatches the shared jitted body, and slices — safe under
    ``jax.transfer_guard("disallow")`` once warm.
    """
    cfg: PipelineConfig           # resolved: "auto" only with tiers set
    height: int
    width: int
    batch: int | None             # padded batch bucket; None = single frame
    tiers: tuple[int, ...] | None  # static autotune tiers (iff "auto")

    @classmethod
    def build(cls, cfg: PipelineConfig, height: int, width: int, *,
              batch: int | None = None) -> "DetectionPlan":
        if cfg.fused and not cfg.hough.compact:
            raise ValueError(
                "PipelineConfig.fused requires hough.compact=True: the "
                "fused kernel's output IS the compacted edge list."
            )
        resolved, tiers = resolve_static(cfg, height, width)
        return cls(resolved, height, width, batch, tiers)

    # --- derived plans -------------------------------------------------
    def with_render(self, render: bool) -> "DetectionPlan":
        """The same plan with the render phase bound on or off.

        Rendering is a config-static knob of the jitted body, so each
        value is its own compiled program; binding it at the plan level
        lets callers with per-request render demands (the detection
        service) flip between two frozen plans instead of re-resolving.
        Detection outputs (lines/valid/peaks/edges) are computed by the
        same ops either way — only the extra ``rendered`` field differs.
        """
        if self.cfg.render_output == render:
            return self
        return dataclasses.replace(
            self, cfg=dataclasses.replace(self.cfg, render_output=render)
        )

    def with_theta_band(self, band: int | None) -> "DetectionPlan":
        """The same plan with the prediction-gated vote bound to a static
        band width (``None`` = full sweep).

        Like ``with_render``, the band width is a config-static knob of the
        jitted body — the tracking loop (``core/tracking.py``) holds the
        full plan and its gated twin and flips between them on track
        loss/recovery instead of re-resolving; the gate's *bin values* are
        runtime data passed to ``run``.
        """
        if self.cfg.hough.theta_band == band:
            return self
        return dataclasses.replace(
            self, cfg=dataclasses.replace(
                self.cfg,
                hough=dataclasses.replace(self.cfg.hough, theta_band=band),
            )
        )

    def with_fused(self, corridors: int | None = None) -> "DetectionPlan":
        """The fused-hot-path twin of this plan, optionally with the
        rho-corridor pre-filter bound to a static corridor count.

        Same pattern as ``with_theta_band``: the fused binding and the
        corridor *count* are config-static knobs of the jitted body (one
        compiled program per value), while the corridor *windows* are
        runtime data passed to ``run``.  Callers (the tracking loop, the
        detection service) hold the staged plan and this twin, dispatching
        fused only when the tracker's corridors are healthy — the staged
        plan is the full-sweep fallback on cold start and overflow.
        Requires ``hough.compact=True`` (checked at build).
        """
        cfg = dataclasses.replace(
            self.cfg, fused=True,
            hough=dataclasses.replace(self.cfg.hough, corridors=corridors),
        )
        if cfg == self.cfg:
            return self
        if not cfg.hough.compact:
            raise ValueError(
                "with_fused requires hough.compact=True: the fused "
                "kernel's output IS the compacted edge list."
            )
        return dataclasses.replace(self, cfg=cfg)

    # --- execution ----------------------------------------------------
    def _dispatch(self, images: jax.Array,
                  theta_bins: jax.Array | None = None,
                  corridors: jax.Array | None = None) -> DetectionResult:
        return _detect(self.cfg, images, theta_bins, corridors,
                       tiers=self.tiers)

    def run(self, images, theta_bins=None, corridors=None
            ) -> DetectionResult:
        """Detect on a frame (H, W) or batch (N <= bucket, H, W).

        Batches shorter than the bucket are padded with zero frames (every
        stage is frame-independent, so pad rows never leak into real
        results) and the result is sliced back to the true length.
        ``theta_bins`` — required exactly when the plan's config sets
        ``theta_band`` — is the (theta_band,) int32 prediction gate, shared
        across the batch.  ``corridors`` — required exactly when the
        config sets ``hough.corridors`` (fused plans) — is the
        (corridors, 4) f32 rho-window set, likewise shared.
        """
        if theta_bins is not None:
            theta_bins = jnp.asarray(theta_bins, jnp.int32)
        if corridors is not None:
            corridors = jnp.asarray(corridors, jnp.float32)
        if self.batch is None:
            assert images.shape[-2:] == (self.height, self.width), (
                images.shape, self)
            return self._dispatch(images, theta_bins, corridors)
        n = images.shape[0]
        assert (images.ndim == 3 and n <= self.batch
                and images.shape[-2:] == (self.height, self.width)), (
            images.shape, self)
        if n < self.batch:
            images = jnp.concatenate([
                images,
                jnp.zeros((self.batch - n, self.height, self.width),
                          images.dtype),
            ])
        res = self._dispatch(images, theta_bins, corridors)
        if n == self.batch:
            return res
        return DetectionResult(
            res.lines[:n], res.valid[:n], res.peaks[:n], res.edges[:n],
            None if res.rendered is None else res.rendered[:n],
            None if res.edge_count is None else res.edge_count[:n],
        )

    __call__ = run


class PlanCache:
    """Per-detector memo of plans keyed by ``(H, W, batch-bucket)``.

    ``device`` pins the cache (and everything staged through ``put``) to
    one jax device: a sharded service keeps one PlanCache per replica, so
    each replica's dispatches compile and run on its own device instead
    of whatever the backend default is.  ``None`` keeps the pre-mesh
    behavior (default device, plain ``jax.device_put``).
    """

    def __init__(self, cfg: PipelineConfig, *, device=None):
        self.cfg = cfg
        self.device = device
        self._plans: dict[tuple[int, int, int | None], DetectionPlan] = {}

    def put(self, x):
        """Ship a host batch to this cache's device (the one explicit
        transfer per dispatch — callers keep their hot loops under
        ``jax.transfer_guard("disallow")``)."""
        if self.device is None:
            return jax.device_put(x)
        return jax.device_put(x, self.device)

    def plan_for(self, height: int, width: int, *,
                 batch: int | None = None) -> DetectionPlan:
        key = (height, width, batch)
        plan = self._plans.get(key)
        if plan is None:
            plan = DetectionPlan.build(self.cfg, height, width, batch=batch)
            self._plans[key] = plan
        return plan

    def __len__(self) -> int:
        return len(self._plans)
