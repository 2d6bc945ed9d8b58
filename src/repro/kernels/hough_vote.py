"""GEMM-form Hough voting Pallas kernel (paper Algorithm 2, re-architected).

The paper *keeps Hough on the scalar core*: its voting loop carries CPI > 3
serial dependencies (``accumulators[idx]++``) that even the OoO BOOM core
cannot hide, so Gemmini gives it nothing (Table 7: 1.07x-1.16x).

The TPU adaptation dissolves the dependency instead of tolerating it:

  1. ``rho[p, theta] = x_p * cos(theta) + y_p * sin(theta)`` for *all* edge
     pixels and angles at once — the ``(n_pix, C) @ (C, n_theta)`` product
     with C = 3 homogeneous coordinates, evaluated as broadcast
     multiply-adds on the VPU (C = 3 would fill 3 rows of the MXU).
  2. The vote histogram becomes one-hot products on the MXU.  Each rho bin
     ``r = floor(rho)`` splits into ``hi = r // 128`` and ``lo = r % 128``,
     and for each theta ``t``
     ``votes_t[hi, lo] = sum_p (w_p [hi_pt == hi]) [lo_pt == lo]``, a
     ``(n_hi, bp) x (bp, 128)`` contraction over the pixel block with 0/1
     bf16 operands and an exact f32 accumulator.  The VPU builds
     ``n_hi + 128`` one-hot entries per (pixel, theta) instead of comparing
     against every rho bin; no serialized read-modify-write anywhere.  A
     bin below 0 has a negative ``hi`` and matches no row; a bin at or
     past ``n_rho`` matches no row or lands past ``n_rho`` in the padded
     accumulator, which is cut off.

Grid: ``(batch, theta_blocks, pixel_blocks)`` with pixels innermost so the
``(bt, n_hi, 128)`` vote block stays output-stationary in VMEM (same
dataflow as ``tiled_matmul``).  Thetas sit on sublanes and pixels on lanes,
so a theta's bins form one row and each one-hot is a sublane broadcast
against an iota.  The leading batch axis lowers a stack of frames as one
kernel; shared pixel coordinates (the uncompacted dense raster) are
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


def _vote_kernel(xy_ref, w_ref, trig_ref, o_ref, *, n_hi):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    C, bp = xy_ref.shape[-2:]
    xy = xy_ref[...].reshape(C, bp)      # (C, bp) pixel coordinates
    w = w_ref[...].reshape(1, bp)        # (1, bp) edge weights (0 => skip)
    trig = trig_ref[...]                 # (bt, C) cos/sin(/offset) rows

    # Stage 1: rho for this (theta block, pixel block), thetas on sublanes.
    # K = C <= 3 would fill 3 of the MXU's 128 contraction rows, so it is C
    # broadcast multiply-adds on the VPU, in f32, in column order.  The
    # oracle's f32 dot on the CPU fuses the second product into a
    # multiply-add, so where rho lies within an ulp of a bin edge the two
    # can bin a vote one rho bin apart (about 4 in 10**6 pixel-theta pairs
    # over a 480x640 frame).
    rho = xy[0:1, :] * trig[:, 0:1]
    for c in range(1, C):
        rho = rho + xy[c : c + 1, :] * trig[:, c : c + 1]     # (bt, bp)
    r = jnp.floor(rho).astype(jnp.int32)
    hi = r >> 7      # arithmetic shift: a bin below 0 keeps a negative hi
    lo = r & 127

    # Stage 2: per theta, votes[hi, lo] = sum_p (w_p [hi_p == hi]) [lo_p == lo]
    # on the MXU.  Both operands are 0/1 (the weight folded into the high
    # one-hot) and exact in bf16; the f32 accumulator is exact.
    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (n_hi, bp), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (128, bp), 0)
    for t in range(trig.shape[0]):
        a = jnp.where(hi[t : t + 1] == hi_ids, w, 0.0).astype(jnp.bfloat16)
        b = (lo[t : t + 1] == lo_ids).astype(jnp.bfloat16)
        o_ref[0, t] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit, static_argnames=("n_rho", "bp", "bt", "interpret"))
def hough_vote(
    xy: jax.Array,
    weights: jax.Array,
    trig: jax.Array,
    *,
    n_rho: int,
    bp: int = 4096,
    bt: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Accumulate Hough votes.

    Args:
      xy:      (n_pix, C) f32 pixel coordinates — C=2 for raw (x, y), or C=3
               homogeneous ``(x, y, 1)`` so the rho offset/resolution folds
               into the GEMM and ``floor(xy @ trig)`` lands in ``[0, n_rho)``.
               May be (N, n_pix, C) for per-frame (e.g. compacted) pixel
               sets; a single (n_pix, C) set is shared across a weight batch.
      weights: (n_pix,) vote weight per pixel (0 for non-edge pixels — this
               is how variable-length edge sets stay statically shaped), or
               (N, n_pix) for a batch of frames lowered as one kernel.  The
               MXU takes them at bf16: exact for the 0/1 edge masks callers
               pass (and integers up to 256).
      trig:    (C, n_theta) f32, rows ``cos(theta)`` / ``sin(theta)`` (and
               the offset row for C=3) already divided by the rho resolution.
      n_rho:   number of rho bins; a vote whose bin falls outside
               ``[0, n_rho)`` is dropped.
      bp/bt:   pixel / theta block sizes.  Pixels are padded to a multiple
               of ``bp`` (itself a multiple of the 128 lanes) and thetas to
               a multiple of ``bt`` (of the 8 sublanes), so any ``n_theta``
               works (a 40-bin gate, the 180-bin sweep).

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

    n_hi = _round_up(pl.cdiv(n_rho, 128), 8)
    bp = min(_round_up(bp, 128), _round_up(n_pix, 128))
    bt = min(_round_up(bt, 8), _round_up(n_theta, 8))
    P = _round_up(n_pix, bp)
    T = _round_up(n_theta, bt)
    pad = [(0, 0)] * (xy.ndim - 2) + [(0, P - n_pix), (0, 0)]
    xy_t = jnp.swapaxes(jnp.pad(xy.astype(jnp.float32), pad), -1, -2)
    w3 = jnp.pad(weights.astype(jnp.float32), ((0, 0), (0, P - n_pix)))
    w3 = w3[:, None, :]
    trig_t = jnp.pad(trig.astype(jnp.float32), ((0, 0), (0, T - n_theta))).T

    if shared_xy:
        xy_spec = pl.BlockSpec((C, bp), lambda n, t, p: (0, p))
    else:
        xy_spec = pl.BlockSpec((1, C, bp), lambda n, t, p: (n, 0, p))

    out = pl.pallas_call(
        functools.partial(_vote_kernel, n_hi=n_hi),
        grid=(N, T // bt, P // bp),
        in_specs=[
            xy_spec,
            pl.BlockSpec((1, 1, bp), lambda n, t, p: (n, 0, p)),
            pl.BlockSpec((bt, C), lambda n, t, p: (t, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, bt, n_hi, 128), lambda n, t, p: (n, t, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((N, T, n_hi, 128), jnp.float32),
        interpret=interpret,
    )(xy_t, w3, trig_t)
    # (N, theta, hi, lo) -> (N, rho, theta); votes past n_rho go here.
    out = out.reshape(N, T, n_hi * 128)[:, :n_theta, :n_rho]
    out = jnp.swapaxes(out, 1, 2)
    return out[0] if squeeze else out
