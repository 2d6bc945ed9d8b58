"""Fused canny -> threshold -> corridor filter Pallas kernel (kernel A).

The staged hot path runs the gradient convs, the Canny VPU stages and the
Hough threshold as separate dispatches, and each round-trips HBM: the
gradient stack, the magnitude and the edge map are materialized as full
(H, W) arrays between them.  Kernel A computes the whole Canny front end
for one frame in VMEM, thresholds it to vote weights, and zeroes the
weights of pixels outside every tracker corridor.  The only HBM traffic is
the frame in and one f32 weight map out; no gradient, magnitude or edge
map ever reaches HBM.

After it, plain XLA counts the surviving edges exactly, picks the
compaction tier and compacts in raster order (``ref.compact_raster``), and
kernel B (``hough_vote``) votes over the compacted list.  A prefix sum and
a scatter do not map onto the TPU's vector unit, and the exact count is
what keeps the fused path **bit-exact** with the staged one: same count,
same tier, same raster order.

Grid is ``(batch,)`` with one full frame per step (240x320 .. 480x640 f32
fit VMEM whole).  The body is written against the TPU's layout rules:

  * the frame is zero-padded to whole ``(8, 128)`` tiles and every
    neighbour read is a sublane/lane rotate (``pltpu.roll``) masked to the
    real frame, which is exactly the zero-filled shift of the staged path;
  * the convs are tap sums on the VPU with the integer-valued masks of
    ``core.canny.gradient_masks``: on integer-valued frames every sum is an
    exact f32, so the gradients equal the staged MXU GEMM's bit for bit;
  * the Canny stages after the gradients are ``core.canny.edge_mask``
    itself, handed the rotate as its shift;
  * the corridor test reads the snapped normals of
    ``ref.snap_corridors``, so its products are exact, as in the oracle.

It supports the f32 gradient tier (``integer=False``, ``grad_dtype="f32"``,
either mask layout, either variant); any other tier raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import snap_corridors
from .tiles import round_up as _round_up

# Whole-frame residency: at 480x640 the body keeps ~20 f32 frame-sized
# temporaries live, past the 16 MB default scoped-VMEM budget.  A v5e core
# has 128 MiB of VMEM.
_VMEM_LIMIT = 96 * 1024 * 1024


class _RollShift:
    """``shift(x, dy, dx)[i, j] = x[i + dy, j + dx]`` inside the real
    ``(height, width)`` frame, else 0: a sublane/lane rotate of the padded
    tile plus a mask.  The masks are built once per offset and reused."""

    def __init__(self, shape, height, width):
        self.rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        self.cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        self.height, self.width = height, width
        self._ok = {}

    def _valid(self, dy, dx):
        if (dy, dx) not in self._ok:
            ok = None
            if dy:
                r = self.rows + dy
                ok = (r >= 0) & (r < self.height)
            if dx:
                c = self.cols + dx
                okc = (c >= 0) & (c < self.width)
                ok = okc if ok is None else ok & okc
            self._ok[dy, dx] = ok
        return self._ok[dy, dx]

    def __call__(self, x, dy, dx):
        if x.dtype == jnp.bool_:  # rotate as int32: i1 vectors do not
            return self(x.astype(jnp.int32), dy, dx) != 0
        Hp, Wp = x.shape
        out = x
        if dy:
            out = pltpu.roll(out, (-dy) % Hp, 0)
        if dx:
            out = pltpu.roll(out, (-dx) % Wp, 1)
        if not (dy or dx):
            return out
        return jnp.where(self._valid(dy, dx), out, jnp.zeros_like(out))


def _taps(img, masks, shift):
    """Same-padded correlation of ``img`` with each (kh, kw) mask: a VPU
    sum over the nonzero taps (masks are host constants)."""
    outs = []
    for mask in masks:
        kh, kw = mask.shape
        acc = None
        for dy in range(kh):
            for dx in range(kw):
                tap = float(mask[dy, dx])
                if tap == 0.0:
                    continue
                term = tap * shift(img, dy - kh // 2, dx - kw // 2)
                acc = term if acc is None else acc + term
        outs.append(acc)
    return outs


def _weights_kernel(img_ref, cor_ref, o_ref, *, cfg, edge_threshold,
                    height, width, n_corridors):
    from repro.core.canny import edge_mask, gradient_masks  # cycle

    img = img_ref[0]
    shift = _RollShift(img.shape, height, width)
    masks = gradient_masks(cfg)
    if cfg.fused:
        _, gx, gy = _taps(img, masks[0], shift)
    else:
        (s,) = _taps(img, masks[0], shift)
        gx, gy = _taps(s, masks[1], shift)
    strong = edge_mask(gx, gy, cfg, shift=shift, region=(height, width),
                       carry=jnp.int32)
    # The staged weight is ``edges >= threshold`` on a {0, 255} edge map.
    w = jnp.where(strong, float(255.0 >= edge_threshold),
                  float(0.0 >= edge_threshold))
    if n_corridors:
        xx = jax.lax.broadcasted_iota(jnp.int32, img.shape, 1)
        yy = jax.lax.broadcasted_iota(jnp.int32, img.shape, 0)
        xx, yy = xx.astype(jnp.float32), yy.astype(jnp.float32)
        keep = None
        for k in range(n_corridors):
            rho = xx * cor_ref[k, 0] + yy * cor_ref[k, 1]
            hit = (rho >= cor_ref[k, 2]) & (rho <= cor_ref[k, 3])
            keep = hit if keep is None else keep | hit
        w = jnp.where(keep, w, 0.0)
    o_ref[0] = w


@functools.partial(
    jax.jit, static_argnames=("cfg", "edge_threshold", "interpret"),
)
def fused_weights(image: jax.Array, corridors: jax.Array | None = None, *,
                  cfg, edge_threshold: float, interpret: bool = False):
    """Kernel A: frame(s) -> thresholded, corridor-filtered edge weights.

    Args:
      image:     (H, W) or (N, H, W) frame stack.
      corridors: optional (C, 4) rho windows (``ref.corridor_keep`` rows),
                 shared across the batch; None disables filtering.
      cfg:       ``CannyConfig`` (f32 gradient tier; its ``impl`` is
                 irrelevant, the body has one implementation).
      edge_threshold: vote-weight threshold on the {0, 255} canny output
                 (the staged ``HoughConfig.edge_threshold``).

    Returns (..., H*W) f32 weights in raster order, matching
    ``ref.fused_weights``.
    """
    if cfg.integer or cfg.grad_dtype != "f32":
        raise ValueError(
            "the fused kernel implements the f32 gradient tier only "
            f"(got integer={cfg.integer}, grad_dtype={cfg.grad_dtype!r})"
        )
    squeeze = image.ndim == 2
    if squeeze:
        image = image[None]
    N, H, W = image.shape
    Hp, Wp = _round_up(H, 8), _round_up(W, 128)
    img = jnp.pad(image.astype(jnp.float32),
                  ((0, 0), (0, Hp - H), (0, Wp - W)))
    n_corridors = 0 if corridors is None else corridors.shape[0]
    cor = (jnp.zeros((1, 4), jnp.float32) if corridors is None
           else snap_corridors(corridors))

    w = pl.pallas_call(
        functools.partial(
            _weights_kernel, cfg=cfg, edge_threshold=edge_threshold,
            height=H, width=W, n_corridors=n_corridors,
        ),
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp), lambda n: (n, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, Hp, Wp), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Hp, Wp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(img, cor)
    w = w[:, :H, :W].reshape(N, H * W)
    return w[0] if squeeze else w
