"""Canny edge detection in conv-as-GEMM form (paper Section 4.1 / Algorithm 1).

The paper's hot loop — 87.6% of line-detection time (Table 3) — is the Canny
stage, whose stencils it rewrites as mask x neighbourhood matrix products for
Gemmini.  Here the same stages lower to the ``conv2d_gemm`` Pallas kernel
(MXU) while the control-heavy stages (thresholding, non-max suppression,
hysteresis) stay element-wise (VPU) — the TPU version of the paper's
core/accelerator partition, decided by ``core.offload``.

Two execution variants:
  * ``paper``   — faithful to the paper's Algorithm 1: gradient-magnitude
    threshold, direction quantization, double threshold, one-step hysteresis.
  * ``full``    — textbook Canny with direction-aware non-max suppression and
    iterative hysteresis (better lines; used by default in the pipeline).

Two arithmetic modes (paper Section 4.4):
  * float (f32) and integer (uint8 image -> int32 accumulation, L1 gradient
    magnitude, tan-ratio direction tests) — the paper's float->int rewrite,
    validated for detection parity in tests.

One beyond-paper fusion (see ROADMAP.md): ``fused=True`` composes the
Gaussian into the Sobel masks offline (convolution associativity), so one
im2col GEMM pass with 7x7 masks replaces the two chained 5x5 passes — one
pass over HBM instead of two, and wider GEMMs that fill the MXU.

Batched fast path: every stage operates on ``(..., H, W)``, so a stack of
frames ``(N, H, W)`` flows through unchanged — the conv-GEMM kernel lowers
the batch as a leading grid axis and the elementwise stages broadcast.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

# The classic integer-friendly 5x5 Gaussian (sums to 159) and Sobel masks.
GAUSS_5x5 = np.array(
    [
        [2, 4, 5, 4, 2],
        [4, 9, 12, 9, 4],
        [5, 12, 15, 12, 5],
        [4, 9, 12, 9, 4],
        [2, 4, 5, 4, 2],
    ],
    np.float32,
)
GAUSS_NORM = 159.0
SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32)
SOBEL_Y = SOBEL_X.T.copy()

# tan(22.5 deg) and tan(67.5 deg) as integer ratios (paper's int rewrite:
# direction tests become cross-multiplications, no arctan anywhere).
TAN_22_NUM, TAN_22_DEN = 53, 128     # 53/128  = 0.4141 ~ tan 22.5
TAN_67_NUM, TAN_67_DEN = 309, 128    # 309/128 = 2.4141 ~ tan 67.5


def _pad_to(mask: np.ndarray, k: int) -> np.ndarray:
    p = (k - mask.shape[0]) // 2
    return np.pad(mask, ((p, p), (p, p)))


def _compose(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Full 2-D convolution of two masks (associativity: (a*b)*img == a*(b*img))."""
    ka, kb = a.shape[0], b.shape[0]
    k = ka + kb - 1
    out = np.zeros((k, k), np.float32)
    for i in range(ka):
        for j in range(ka):
            out[i : i + kb, j : j + kb] += a[i, j] * b
    return out


@functools.cache
def fused_masks() -> np.ndarray:
    """(3, 7, 7): [gauss(padded), gauss(*)sobel_x, gauss(*)sobel_y]."""
    g = GAUSS_5x5 / GAUSS_NORM
    return np.stack(
        [
            _pad_to(g, 7),
            _compose(g, SOBEL_X),
            _compose(g, SOBEL_Y),
        ]
    ).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class CannyConfig:
    low: float = 40.0          # weak-edge threshold (on 0..255 magnitudes)
    high: float = 90.0         # strong-edge threshold
    variant: str = "full"      # "full" | "paper"
    integer: bool = False      # paper Section 4.4 float->int rewrite
    fused: bool = False        # beyond-paper single-pass 7x7 masks
    hysteresis_iters: int = 8
    border: int = 4            # suppress zero-padding artifacts at the rim
    impl: str | None = None    # kernel dispatch (None => backend default)
    # Gradient-accumulation tier: "f32" (exact, the bit-exactness contract),
    # "f16" (half-precision conv accumulation), or "int8" (per-frame
    # symmetric quantization via core.quantize + integer convs).  The
    # threshold compare downstream always happens on f32 magnitudes; the
    # low-precision tiers trade gradient accuracy for bandwidth and are
    # quality-gated by the quantized F1 floors in scripts/check_f1.py.
    grad_dtype: str = "f32"    # "f32" | "f16" | "int8"


@functools.cache
def gradient_masks(cfg: CannyConfig) -> tuple[np.ndarray, ...]:
    """The conv-mask constants ``_gradients`` needs for ``cfg``, in order.

    Exposed so the fused detection kernel computes its taps from the same
    constants.  The f32 tier gets integer-valued masks, so on integer-valued
    frames (every uint8 camera frame) each conv sum is an exact integer in
    f32 -- below 2**24 for 0..255 inputs -- and the gradients are
    bit-identical on every backend and in every summation order: XLA on
    the CPU, the MXU at HIGHEST precision, the VPU tap sums of the fused
    kernel.  ``thresholds`` folds the Gaussian's 1/GAUSS_NORM into the
    Canny thresholds instead.  The f16 and int8 tiers keep the normalized
    Gaussian they were tuned with.
    """
    if cfg.integer or cfg.grad_dtype == "int8":
        if cfg.fused:
            return (np.round(fused_masks() * GAUSS_NORM).astype(np.int32),)
        return (
            GAUSS_5x5.astype(np.int32)[None],
            np.stack([SOBEL_X, SOBEL_Y]).astype(np.int32),
        )
    if cfg.grad_dtype == "f16":
        if cfg.fused:
            return (fused_masks().astype(np.float16),)
        return (
            (GAUSS_5x5 / GAUSS_NORM)[None].astype(np.float16),
            np.stack([SOBEL_X, SOBEL_Y]).astype(np.float16),
        )
    if cfg.fused:
        return (np.round(fused_masks() * GAUSS_NORM).astype(np.float32),)
    return (GAUSS_5x5[None], np.stack([SOBEL_X, SOBEL_Y]))


def _check_grad_tier(cfg: CannyConfig) -> None:
    if cfg.grad_dtype not in ("f32", "f16", "int8"):
        raise ValueError(f"unknown grad_dtype {cfg.grad_dtype!r}")
    if cfg.integer and cfg.grad_dtype != "f32":
        raise ValueError(
            "grad_dtype tiers apply to the float pipeline; the integer "
            "rewrite (integer=True) is its own arithmetic mode"
        )


def _gradients(image: jax.Array, cfg: CannyConfig):
    """Stages 1-2: noise reduction + intensity gradient, all GEMM-form.

    ``image`` is (..., H, W); conv outputs stack masks on axis -3.
    Whatever the accumulation tier, ``gx``/``gy`` come back as f32 (int32
    for the paper's integer rewrite) so the threshold compare downstream
    is always full-precision.  The f32 tier returns the raw integer-mask
    sums, GAUSS_NORM times the normalized values (see ``gradient_masks``).
    """
    _check_grad_tier(cfg)
    masks = tuple(jnp.asarray(m) for m in gradient_masks(cfg))

    if cfg.integer:
        img = image.astype(jnp.int32)
        if cfg.fused:
            # Integer fusion: scale fused float masks to int (x GAUSS_NORM).
            out = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)
            nr = out[..., 0, :, :] // int(GAUSS_NORM)
            gx = out[..., 1, :, :] // int(GAUSS_NORM)
            gy = out[..., 2, :, :] // int(GAUSS_NORM)
        else:
            nr = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)[
                ..., 0, :, :
            ] // int(GAUSS_NORM)
            gxy = ops.conv2d_gemm(nr, masks[1], impl=cfg.impl)
            gx, gy = gxy[..., 0, :, :], gxy[..., 1, :, :]
        return nr, gx, gy

    if cfg.grad_dtype == "int8":
        # Per-frame symmetric int8 (core.quantize): integer convs with int32
        # accumulation, dequantized back to f32 between stages so the
        # Gaussian's output re-quantizes at its own dynamic range.
        from .quantize import quantize_frames  # function-level: no cycle

        q = quantize_frames(image)
        if cfg.fused:
            out = ops.conv2d_gemm(q.values, masks[0], impl=cfg.impl)
            s = q.scale / GAUSS_NORM
            nr = out[..., 0, :, :].astype(jnp.float32) * s
            gx = out[..., 1, :, :].astype(jnp.float32) * s
            gy = out[..., 2, :, :].astype(jnp.float32) * s
            return nr, gx, gy
        nr_q = ops.conv2d_gemm(q.values, masks[0], impl=cfg.impl)[
            ..., 0, :, :
        ]
        nr = nr_q.astype(jnp.float32) * (q.scale / GAUSS_NORM)
        q2 = quantize_frames(nr)
        gxy = ops.conv2d_gemm(q2.values, masks[1], impl=cfg.impl)
        gx = gxy[..., 0, :, :].astype(jnp.float32) * q2.scale
        gy = gxy[..., 1, :, :].astype(jnp.float32) * q2.scale
        return nr, gx, gy

    if cfg.grad_dtype == "f16":
        img = image.astype(jnp.float16)
        if cfg.fused:
            out = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)
            return tuple(
                out[..., k, :, :].astype(jnp.float32) for k in range(3)
            )
        nr16 = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)[..., 0, :, :]
        gxy = ops.conv2d_gemm(nr16, masks[1], impl=cfg.impl)
        return (
            nr16.astype(jnp.float32),
            gxy[..., 0, :, :].astype(jnp.float32),
            gxy[..., 1, :, :].astype(jnp.float32),
        )

    img = image.astype(jnp.float32)
    if cfg.fused:
        out = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)
        s, gx, gy = out[..., 0, :, :], out[..., 1, :, :], out[..., 2, :, :]
    else:
        s = ops.conv2d_gemm(img, masks[0], impl=cfg.impl)[..., 0, :, :]
        gxy = ops.conv2d_gemm(s, masks[1], impl=cfg.impl)
        gx, gy = gxy[..., 0, :, :], gxy[..., 1, :, :]
    return s, gx, gy


def _sum_squares(gx, gy):
    """``gx*gx + gy*gy`` in f32, with the same bits on every backend.

    XLA on the CPU contracts ``a*a + b*b`` into a fused multiply-add, and a
    backend that does not rounds differently.  Each gradient is split as
    ``h + l`` with ``h`` a multiple of 512: on integer-valued gradients
    below 2**18 (the f32 tier's raw conv sums) every product and the two
    inner sums are exact, so contraction changes nothing and only the last
    two adds round, in this fixed order.
    """
    hx, hy = (jnp.floor(g * (1.0 / 512) + 0.5) * 512 for g in (gx, gy))
    lx, ly = gx - hx, gy - hy
    return ((hx * hx + hy * hy) + 2 * (hx * lx + hy * ly)) + (
        lx * lx + ly * ly)


def _magnitude_direction(gx, gy, integer: bool):
    """Stage 2b: |G| and direction bin in {0, 45, 90, 135} (VPU work).

    The integer pipeline takes the L1 magnitude; the float pipeline the
    squared L2 magnitude (``thresholds`` squares the Canny thresholds to
    match: no sqrt, which a TPU does not round correctly).  Both test the
    direction by cross-multiplied tan ratios (no arctan, no divide).
    """
    ax, ay = jnp.abs(gx), jnp.abs(gy)
    mag = ax + ay if integer else _sum_squares(gx, gy)
    d0 = TAN_22_DEN * ay < TAN_22_NUM * ax            # ~horizontal grad
    d90 = TAN_67_DEN * ay >= TAN_67_NUM * ax          # ~vertical grad
    diag = jnp.logical_not(d0 | d90)
    same_sign = (gx >= 0) == (gy >= 0)
    # bins: 0 => E-W neighbour pair, 1 => NE-SW, 2 => N-S, 3 => NW-SE
    dirs = jnp.where(
        d0, 0, jnp.where(d90, 2, jnp.where(same_sign & diag, 1, 3))
    ).astype(jnp.int32)
    return mag, dirs


def thresholds(cfg: CannyConfig) -> tuple[float, float]:
    """(low, high) in the units of ``_magnitude_direction``'s magnitude."""
    if cfg.integer:
        return cfg.low, cfg.high
    # The f32 tier's gradients are raw conv sums, GAUSS_NORM x intensity.
    unit = GAUSS_NORM if cfg.grad_dtype == "f32" else 1.0
    return (cfg.low * unit) ** 2, (cfg.high * unit) ** 2


def _shift(x, dy, dx):
    """Zero-filled spatial shift over the trailing (H, W) axes:
    ``out[i, j] = x[i + dy, j + dx]``."""
    H, W = x.shape[-2:]
    pad = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)])
    return pad[..., 1 + dy : 1 + dy + H, 1 + dx : 1 + dx + W]


def _nms(mag, dirs, shift):
    """Direction-aware non-max suppression (full variant, stage 3)."""
    pairs = [((0, 1), (0, -1)), ((-1, 1), (1, -1)),
             ((1, 0), (-1, 0)), ((1, 1), (-1, -1))]
    keep = jnp.zeros_like(mag, dtype=bool)
    for b, (p, q) in enumerate(pairs):
        n1 = shift(mag, *p)
        n2 = shift(mag, *q)
        keep = keep | ((dirs == b) & (mag >= n1) & (mag >= n2))
    return jnp.where(keep, mag, 0)


def _dilate3(x, shift):
    """3x3 binary dilation, separably: a row pass, then a column pass."""
    rows = x | shift(x, 0, -1) | shift(x, 0, 1)
    return rows | shift(rows, -1, 0) | shift(rows, 1, 0)


def _clear_border(x: jax.Array, b: int, height: int, width: int
                  ) -> jax.Array:
    """Zero ``x`` outside ``[b, height - b) x [b, width - b)``."""
    if b <= 0 and (height, width) == x.shape[-2:]:
        return x
    yy = jax.lax.broadcasted_iota(jnp.int32, x.shape[-2:], 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, x.shape[-2:], 1)
    inside = (yy >= b) & (yy < height - b) & (xx >= b) & (xx < width - b)
    return jnp.where(inside, x, jnp.zeros_like(x))


def edge_mask(gx, gy, cfg: CannyConfig, *, shift=_shift, region=None,
              carry=jnp.bool_):
    """Stages 2b-5 on gradients: the boolean Canny edge map.

    ``shift`` is the zero-filled neighbour shift (``_shift`` here; the
    fused kernel passes a lane/sublane rotate).  ``region`` is the
    ``(height, width)`` of the real frame when ``gx`` is padded beyond it;
    everything outside it is cleared like the border.  ``carry`` is the
    dtype of the hysteresis loop's carry: the fused kernel passes int32,
    since the TPU compiler cannot carry a boolean vector.
    """
    height, width = region or gx.shape[-2:]
    mag, dirs = _magnitude_direction(gx, gy, cfg.integer)
    mag = _clear_border(mag, cfg.border, height, width)
    low, high = thresholds(cfg)

    if cfg.variant == "paper":
        # Algorithm 1 stages 3-5: pure thresholds, one hysteresis pass.
        edge = mag >= low
        strong = edge & (mag >= high)
        return strong | (edge & _dilate3(strong, shift))

    sup = _nms(mag, dirs, shift)
    strong = sup >= high
    weak = (sup >= low) & ~strong

    def body(_, s):
        s = s.astype(bool)
        return (s | (weak & _dilate3(s, shift))).astype(carry)

    grown = jax.lax.fori_loop(0, cfg.hysteresis_iters, body,
                              strong.astype(carry))
    return grown.astype(bool)


def canny(image: jax.Array, cfg: CannyConfig = CannyConfig()) -> jax.Array:
    """Edge map (..., H, W) uint8 in {0, 255} (paper's ``image_out``).

    Accepts a single frame (H, W) or a batch (N, H, W) — the batch lowers
    through the conv kernel as one launch and the VPU stages broadcast.
    """
    _, gx, gy = _gradients(image, cfg)
    return jnp.where(edge_mask(gx, gy, cfg), 255, 0).astype(jnp.uint8)


canny_jit = jax.jit(canny, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg", "stride", "margin"))
def estimate_edge_count_device(image: jax.Array,
                               cfg: CannyConfig = CannyConfig(), *,
                               stride: int = 2, margin: float = 2.5
                               ) -> jax.Array:
    """Device-side downsampled-gradient edge-count bound (int32 scalar).

    The traced body of :func:`estimate_edge_count`: the image is subsampled
    by ``stride``, finite differences stand in for Sobel-of-Gaussian
    (``kernels.ops.grad_hits``), and coarse hits are scaled by
    ``stride * margin`` into an upper bound on the post-NMS Canny edge
    count.  Runs entirely on the device; batches reduce to the max
    per-frame estimate.  This pre-Canny estimate backs the legacy host
    resolver (``LineDetector.resolve_config`` — one readback, outside any
    hot loop); the plan path doesn't need it, because its jitted body has
    the actual edge map and tier-dispatches on the exact device-side count
    (``core.hough.hough_transform_tiered``).  ``tests/test_scenarios.py``
    validates the bound (estimate >= actual edge count) on every family.
    """
    # low/2, floored at 20: contrast below that never survives the double
    # threshold, and 20 sits >3 sigma above asphalt-texture differences so
    # the count tracks strokes/speckle, not ground-plane noise.
    thresh = max(cfg.low / 2.0, 20.0)
    hits = ops.grad_hits(image, stride=stride, thresh=thresh, impl=cfg.impl)
    worst = hits.max().astype(jnp.float32)
    return jnp.floor(worst * stride * margin).astype(jnp.int32) + 64


def estimate_edge_count(image, cfg: CannyConfig = CannyConfig(), *,
                        stride: int = 2, margin: float = 2.5) -> int:
    """Cheap downsampled gradient pass: upper-bound the Canny edge count.

    Sizes the Hough edge-compaction buffer (``HoughConfig(max_edges="auto")``)
    *before* the jitted pipeline runs, so the buffer is a static shape.  Each
    coarse hit represents at most ~``stride`` post-NMS edge pixels per stroke
    side, and ``margin`` absorbs the both-sides-of-a-stroke factor plus
    speckle that subsampling undercounts.

    Accepts a single frame (H, W) or a batch (N, H, W): batches return the
    max per-frame estimate, since the compaction buffer is shared.  This is
    the *host* entry point — it runs :func:`estimate_edge_count_device` and
    reads the scalar back, so it must see concrete values (never call under
    jit; the plan layer keeps the device value traced instead).
    """
    if isinstance(image, jax.core.Tracer):
        raise ValueError(
            "estimate_edge_count reads the estimate back to the host; under "
            "jit use estimate_edge_count_device (core/plan.py does)."
        )
    return int(estimate_edge_count_device(image, cfg, stride=stride,
                                          margin=margin))
