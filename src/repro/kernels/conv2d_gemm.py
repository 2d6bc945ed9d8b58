"""Fused multi-mask conv-as-GEMM Pallas kernel (paper Section 4 / Workload 3).

The paper rewrites the Canny stencils (5x5 Gauss mask, Sobel masks) as matrix
multiplications — a 5x5 mask times a 5x5 per-pixel neighbourhood — and ships
them to Gemmini.  Its reported limitation is that 5x5 operands underfill the
16x16 systolic array.

This kernel is the TPU-native fix: every mask row becomes a banded
(Toeplitz) matrix, so a ``(bh, 3*bw)`` strip of halo rows times one
``(3*bw, n_masks*bw)`` band is that mask row applied to every pixel of the
tile for **all masks at once** — ``kh`` aligned MXU GEMMs per tile, with the
row offsets taken by a sublane rotate.  Nothing is re-laid out in VMEM (an
im2col onto a new minor axis is a reshape the TPU compiler refuses), the
patches never touch HBM, and all Canny masks of one pass (Gauss, or Sobel-x
and Sobel-y, or the three fused 7x7 masks) share the strip.

Streaming layout (the batched fast path):
  * the grid is ``(batch, row_block, col_block)`` — a leading batch axis so a
    stack of frames lowers as **one** kernel launch, and a 2-D spatial tiling
    so per-step VMEM is O(bh * bw), independent of the image size.  This
    removes the old whole-image-VMEM-residency ceiling (a 1080p f32 frame is
    ~8 MB *before* im2col; a (bh, bw) tile is a few hundred KB).
  * overlapping stencil windows cannot be expressed as non-overlapping
    BlockSpec tiles, so the halo is streamed by passing the zero-padded image
    through **nine index-mapped BlockSpecs** — the 3x3 neighbourhood of the
    current tile.  The image is padded by one full block on every side so the
    neighbour index maps stay in range and the boundary halos read zeros
    (same-padding semantics for free).  Pallas's pipeline machinery
    double-buffers each neighbour stream from HBM.
  * output is ``(batch, n_masks, H, W)`` so the lane dimension stays W-major.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import round_up as _round_up

# The TPU's MXU multiplies bf16 by default; HIGHEST makes it split each f32
# operand into three bf16 parts, so integer-valued operands of up to 16
# significant bits (uint8 pixels, the integer Gaussian sums that feed the
# Sobel pass) multiply and accumulate exactly.  Mosaic honours it inside the
# kernel (``tpu.contract_precision<fp32>``).
PRECISION = jax.lax.Precision.HIGHEST


def band_matrices(masks: jax.Array, bw: int) -> jax.Array:
    """Banded (Toeplitz) form of ``masks`` for one column tile.

    Returns ``T`` of shape ``(kh, 3*bw, n_masks*bw)`` with
    ``T[dy, bw + j - pw + dx, m*bw + j] = masks[m, dy, dx]``: a row of the
    three-tile-wide halo strip times ``T[dy]`` is mask row ``dy`` of every
    mask correlated along the columns of the centre tile.  Pure gather and
    select, so the entries are the mask values bit for bit.
    """
    n_masks, kh, kw = masks.shape
    pw = kw // 2
    c = np.arange(3 * bw)[:, None]
    j = np.arange(bw)[None, :]
    dx = c - bw - j + pw                      # (3bw, bw) tap column index
    valid = (dx >= 0) & (dx < kw)
    taps = jnp.asarray(masks, jnp.float32)[:, :, np.clip(dx, 0, kw - 1)]
    band = jnp.where(jnp.asarray(valid), taps, 0.0)  # (M, kh, 3bw, bw)
    return band.transpose(1, 2, 0, 3).reshape(kh, 3 * bw, n_masks * bw)


def _conv_kernel(*refs, bh, bw, kh, n_masks):
    # refs: 9 halo-neighbour image blocks (row-major 3x3), band, output.
    nbr, band_ref, o_ref = refs[:9], refs[9], refs[10]
    ph = kh // 2
    rows = [
        jnp.concatenate(
            [nbr[3 * r + c][0].astype(jnp.float32) for c in range(3)],
            axis=1,
        )
        for r in range(3)
    ]
    strip = jnp.concatenate(rows, axis=0)          # (3bh, 3bw) halo strip
    acc = jnp.zeros((bh, n_masks * bw), jnp.float32)
    for dy in range(kh):
        # Rows [bh - ph + dy, 2bh - ph + dy) of the strip, brought to the
        # top by a sublane rotate so the slice stays tile-aligned.
        shift = (2 * bh + ph - dy) % (3 * bh)
        win = pltpu.roll(strip, shift, 0)[:bh] if shift else strip[:bh]
        acc = acc + jnp.dot(win, band_ref[dy], precision=PRECISION,
                            preferred_element_type=jnp.float32)
    for m in range(n_masks):
        o_ref[0, m] = acc[:, m * bw : (m + 1) * bw].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bh", "bw", "out_dtype", "interpret")
)
def conv2d_gemm(
    image: jax.Array,
    masks: jax.Array,
    *,
    bh: int = 16,
    bw: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Same-padded 2D correlation of ``image`` with ``masks`` (n_masks, kh, kw).

    ``image`` may be a single frame ``(H, W)`` -> ``(n_masks, H, W)``, or a
    batch ``(N, H, W)`` -> ``(N, n_masks, H, W)`` lowered as one kernel with
    a leading batch grid axis.

    ``bh``/``bw`` tile the rows/columns; non-multiple shapes are padded up
    and cropped (the TPU needs ``bh % 8 == 0`` and ``bw % 128 == 0``).  The
    GEMM runs in f32 at HIGHEST precision whatever the input dtype, so
    integer inputs (the paper's integer pipeline, the int8 tier) come back
    exact as long as their sums stay below 2**24; the result is cast to
    ``out_dtype`` (int32 for integer inputs, else the input dtype).
    """
    squeeze = image.ndim == 2
    if squeeze:
        image = image[None]
    N, H, W = image.shape
    n_masks, kh, kw = masks.shape
    integer = jnp.issubdtype(image.dtype, jnp.integer)
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else image.dtype

    ph, pw = kh // 2, kw // 2
    bh = max(bh, ph)
    bw = max(min(bw, _round_up(W, 8)), pw)
    Hb, Wb = _round_up(H, bh), _round_up(W, bw)
    # One extra zero block on every side: boundary tiles read their halo
    # from it, and neighbour index maps (i+di, j+dj) never go out of range.
    padded = jnp.pad(
        image, ((0, 0), (bh, Hb - H + bh), (bw, Wb - W + bw))
    )
    band = band_matrices(masks, bw)

    nbr_specs = [
        pl.BlockSpec(
            (1, bh, bw),
            (lambda n, i, j, di=di, dj=dj: (n, i + di, j + dj)),
        )
        for di in range(3)
        for dj in range(3)
    ]
    out = pl.pallas_call(
        functools.partial(_conv_kernel, bh=bh, bw=bw, kh=kh,
                          n_masks=n_masks),
        grid=(N, Hb // bh, Wb // bw),
        in_specs=nbr_specs
        + [pl.BlockSpec(band.shape, lambda n, i, j: (0, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, n_masks, bh, bw), lambda n, i, j: (n, 0, i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((N, n_masks, Hb, Wb), out_dtype),
        interpret=interpret,
    )(*([padded] * 9), band)
    out = out[:, :, :H, :W]
    return out[0] if squeeze else out
