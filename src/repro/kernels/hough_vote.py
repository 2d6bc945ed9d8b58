"""GEMM-form Hough voting Pallas kernel (paper Algorithm 2, re-architected).

The paper *keeps Hough on the scalar core*: its voting loop carries CPI > 3
serial dependencies (``accumulators[idx]++``) that even the OoO BOOM core
cannot hide, so Gemmini gives it nothing (Table 7: 1.07x-1.16x).

The TPU adaptation dissolves the dependency instead of tolerating it:

  1. ``rho[p, theta] = x_p * cos(theta) + y_p * sin(theta)`` for *all* edge
     pixels and angles at once — the ``(n_pix, C) @ (C, n_theta)`` product
     with C = 3 homogeneous coordinates, evaluated as broadcast
     multiply-adds on the VPU (C = 3 would fill 3 rows of the MXU).
  2. The vote histogram becomes a masked reduction: for a rho-bin block
     ``[r0, r0+br)`` and a theta block ``[t0, t0+bt)``,
     ``votes[r, t] = sum_p w_p * [rho_idx[p, t] == r]``, one rho row at a
     time, accumulated in a VMEM-resident ``(br, bt)`` tile.  No
     serialized read-modify-write anywhere, and no ``(br, bp, bt)`` one-hot
     intermediate (at 128 x 256 x 128 it alone would fill the TPU's 16 MB
     of scoped VMEM).

Grid: ``(batch, rho_blocks, theta_blocks, pixel_blocks)`` with pixels
innermost so the vote tile stays output-stationary in scratch (same dataflow
as ``tiled_matmul``).  The leading batch axis lowers a stack of frames as
one kernel; shared pixel coordinates (the uncompacted dense raster) are
broadcast through the index map instead of being materialized per frame.

Edge compaction (the streaming fast path): typically <5% of pixels are edge
pixels, so ``compact_edges`` prefix-sum-scatters the edge coordinates into a
static ``(max_edges, C)`` buffer first and the vote grid iterates compacted
pixels only — the pixel-block axis is bounded by ``max_edges``, not H*W.
The uncompacted dense path stays available (``ops.hough_vote(compact=...)``)
and both are mirrored in ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import round_up as _round_up


def _compact_one(xy: jax.Array, w: jax.Array, max_edges: int):
    """Prefix-sum scatter: edge pixel k lands in compacted row k."""
    mask = w > 0
    pos = jnp.where(mask, jnp.cumsum(mask) - 1, max_edges)
    cxy = (
        jnp.zeros((max_edges, xy.shape[-1]), xy.dtype)
        .at[pos]
        .set(xy, mode="drop")
    )
    cw = jnp.zeros((max_edges,), w.dtype).at[pos].set(w, mode="drop")
    return cxy, cw


@functools.partial(jax.jit, static_argnames=("max_edges",))
def compact_edges(xy: jax.Array, weights: jax.Array, *, max_edges: int):
    """Compact edge pixels (weight > 0) to the front of a static buffer.

    Args:
      xy:      (n_pix, C) coordinates, or (N, n_pix, C) per-frame.
      weights: (n_pix,) or (N, n_pix) vote weights; 0 marks non-edges.
      max_edges: static output length.  Edges beyond it are dropped
        (out-of-bounds scatter, mode="drop") — size it for the workload.

    Returns (cxy, cw) of shape (..., max_edges, C) / (..., max_edges); rows
    past the actual edge count are zero (weight 0 => no vote cast).
    """
    if weights.ndim == 1:
        return _compact_one(xy, weights, max_edges)
    if xy.ndim == 2:  # shared raster coordinates, per-frame weights
        return jax.vmap(lambda w: _compact_one(xy, w, max_edges))(weights)
    return jax.vmap(lambda x, w: _compact_one(x, w, max_edges))(xy, weights)


def _vote_kernel(xy_ref, w_ref, trig_ref, o_ref, acc_ref, *, br):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bp, C = xy_ref.shape[-2:]
    xy = xy_ref[...].reshape(bp, C)      # (bp, C) pixel coordinates
    w = w_ref[...].reshape(bp, 1)        # (bp, 1) edge weights (0 => skip)
    trig = trig_ref[...]                 # (C, bt) cos/sin(/offset) columns

    # Stage 1: rho for this theta block.  K = C <= 3 would fill 3 of the
    # MXU's 128 contraction rows, so it is C broadcast multiply-adds on the
    # VPU, in f32, in column order.  The oracle's f32 dot on the CPU fuses
    # the second product into a multiply-add, so where rho lies within an
    # ulp of a bin edge the two can bin a vote one rho bin apart (about 4
    # in 10**6 pixel-theta pairs over a 480x640 frame).
    rho = xy[:, 0:1] * trig[0:1, :]
    for c in range(1, C):
        rho = rho + xy[:, c : c + 1] * trig[c : c + 1, :]      # (bp, bt)
    rel = jnp.floor(rho).astype(jnp.int32) - pl.program_id(1) * br

    # Stage 2: histogram of this pixel block into the (br, bt) rho block,
    # one rho row at a time (a masked sublane reduction).
    def row(r, carry):
        hits = jnp.sum(jnp.where(rel == r, w, 0.0), axis=0, keepdims=True)
        acc_ref[pl.ds(r, 1), :] += hits
        return carry

    jax.lax.fori_loop(0, br, row, 0)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[...] = acc_ref[...][None].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_rho", "br", "bp", "bt", "interpret")
)
def hough_vote(
    xy: jax.Array,
    weights: jax.Array,
    trig: jax.Array,
    *,
    n_rho: int,
    br: int = 128,
    bp: int = 256,
    bt: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Accumulate Hough votes.

    Args:
      xy:      (n_pix, C) f32 pixel coordinates — C=2 for raw (x, y), or C=3
               homogeneous ``(x, y, 1)`` so the rho offset/resolution folds
               into the GEMM and ``floor(xy @ trig)`` lands in ``[0, n_rho)``.
               May be (N, n_pix, C) for per-frame (e.g. compacted) pixel
               sets; a single (n_pix, C) set is shared across a weight batch.
      weights: (n_pix,) f32 vote weight per pixel (0 for non-edge pixels —
               this is how variable-length edge sets stay statically shaped),
               or (N, n_pix) for a batch of frames lowered as one kernel.
      trig:    (C, n_theta) f32, rows ``cos(theta)`` / ``sin(theta)`` (and
               the offset row for C=3) already divided by the rho resolution.
      n_rho:   number of rho bins.
      br/bp/bt: rho-bin / pixel / theta block sizes.  The theta axis is
               padded to a multiple of ``bt``, so the TPU's 128-lane block
               rule holds for any ``n_theta`` (a 40-bin gate, the 180-bin
               sweep).

    Returns: (n_rho, n_theta) f32 vote accumulator (paper's
    ``accumulators``), with a leading N axis when ``weights`` is batched.
    """
    squeeze = weights.ndim == 1
    if squeeze:
        weights = weights[None]
        if xy.ndim == 3:
            xy = xy[0]
    N, n_pix = weights.shape
    shared_xy = xy.ndim == 2
    C = xy.shape[-1]
    assert xy.shape[-2] == n_pix and C == trig.shape[0], (
        xy.shape, weights.shape, trig.shape,
    )
    n_theta = trig.shape[1]

    bp = min(bp, _round_up(n_pix, 8))
    br = min(br, _round_up(n_rho, 8))
    P = _round_up(n_pix, bp)
    N_rho = _round_up(n_rho, br)
    N_theta = _round_up(n_theta, bt)
    if P != n_pix:
        pad = [(0, 0)] * (xy.ndim - 2) + [(0, P - n_pix), (0, 0)]
        xy = jnp.pad(xy, pad)
        weights = jnp.pad(weights, ((0, 0), (0, P - n_pix)))
    trig = jnp.pad(trig, ((0, 0), (0, N_theta - n_theta)))
    w3 = weights[:, :, None].astype(jnp.float32)

    if shared_xy:
        xy_spec = pl.BlockSpec((bp, C), lambda n, r, t, p: (p, 0))
    else:
        xy_spec = pl.BlockSpec((1, bp, C), lambda n, r, t, p: (n, p, 0))

    out = pl.pallas_call(
        functools.partial(_vote_kernel, br=br),
        grid=(N, N_rho // br, N_theta // bt, P // bp),
        in_specs=[
            xy_spec,
            pl.BlockSpec((1, bp, 1), lambda n, r, t, p: (n, p, 0)),
            pl.BlockSpec((C, bt), lambda n, r, t, p: (0, t)),
        ],
        out_specs=pl.BlockSpec(
            (1, br, bt), lambda n, r, t, p: (n, r, t)
        ),
        out_shape=jax.ShapeDtypeStruct((N, N_rho, N_theta), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, bt), jnp.float32)],
        interpret=interpret,
    )(xy.astype(jnp.float32), w3, trig.astype(jnp.float32))
    out = out[:, :n_rho, :n_theta]
    return out[0] if squeeze else out
