"""Plain reference of the served detection: Canny, corridors, vote, peaks.

Straightforward numpy, written from the algorithm's definition and
independent of the program under test (``src/repro``): it imports none of
it and takes no table, mask or threshold it makes.  It states the
arithmetic the served path promises on uint8 frames:

* **Canny.** Same-size zero-padded correlation with the integer 5x5
  Gaussian (sum 159), then with the integer Sobel pair; every sum is an
  exact integer.  The squared gradient magnitude in f32, summed as
  ``((hx*hx + hy*hy) + 2*(hx*lx + hy*ly)) + (lx*lx + ly*ly)`` with ``h``
  the gradient rounded to a multiple of 512 (only the last two adds
  round).  Direction by the cross-multiplied tan ratios 53/128 and
  309/128 in f32, a 4-pixel border cleared, direction-aware non-max
  suppression, thresholds ``(40*159)**2`` and ``(90*159)**2`` in f32, and
  eight rounds of 3x3 hysteresis growth.
* **Corridors** (a fused dispatch).  Rows ``[cos, sin, rho_lo, rho_hi]``
  with the normal snapped to multiples of 2**-13; a pixel votes if
  ``x*cos + y*sin`` lies in some row's closed window.
* **Edge count and compaction.** Edge pixels in raster order; a frame
  keeps its first ``max(256, H*W // 16)`` (the largest compaction tier),
  whichever smaller tier the batch took.
* **Vote.** ``rho = x*cos + y*sin + diag`` in f32, one rounding per
  operation in that order, binned by ``floor``; theta bin ``k`` is
  ``k * f32(pi/180)`` in f32.  A gated dispatch votes only in the columns
  of its gate (in the gate's order, padding included).
* **Peaks.** Threshold ``max(f32(0.09 * diag), votes.max() / 2)``, a 7x7
  local maximum, the 16 highest scores (ties to the lower flat index),
  valid where the score is positive.

``control`` rounds the Gaussian's output to bfloat16 before the Sobel
pass: a single bfloat16 pass, the TPU's default precision for an f32
matrix product.  (Three passes, ``high``, are exact on these sums: the
largest, 255 * 159, needs 16 bits.)
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

GAUSS = np.array([[2, 4, 5, 4, 2], [4, 9, 12, 9, 4], [5, 12, 15, 12, 5],
                  [4, 9, 12, 9, 4], [2, 4, 5, 4, 2]], np.float32)
SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32)
SOBEL_Y = SOBEL_X.T.copy()
LOW2 = np.float32((40.0 * 159.0) ** 2)
HIGH2 = np.float32((90.0 * 159.0) ** 2)
BORDER = 4
HYSTERESIS_ITERS = 8
N_THETA = 180
MAX_LINES = 16
NEIGHBORHOOD = 7
MIN_VOTES_FRAC = 0.09


def correlate(img: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Same-size correlation with zero padding, in f32.  Exact while every
    partial sum is an integer below 2**24."""
    k = mask.shape[0]
    p = k // 2
    H, W = img.shape
    pad = np.zeros((H + 2 * p, W + 2 * p), np.float32)
    pad[p:p + H, p:p + W] = img
    out = np.zeros((H, W), np.float32)
    for dy in range(k):
        for dx in range(k):
            if mask[dy, dx]:
                out += mask[dy, dx] * pad[dy:dy + H, dx:dx + W]
    return out


def gradients(frame: np.ndarray, *, control: bool = False):
    s = correlate(frame.astype(np.float32), GAUSS)
    if control:
        s = s.astype(ml_dtypes.bfloat16).astype(np.float32)
    return correlate(s, SOBEL_X), correlate(s, SOBEL_Y)


def _sum_squares(gx, gy):
    f = np.float32
    hx = np.floor(gx * f(1 / 512) + f(0.5)) * f(512)
    hy = np.floor(gy * f(1 / 512) + f(0.5)) * f(512)
    lx, ly = gx - hx, gy - hy
    return ((hx * hx + hy * hy) + f(2) * (hx * lx + hy * ly)) + (
        lx * lx + ly * ly)


def _shift(x, dy, dx):
    """``out[i, j] = x[i + dy, j + dx]``, zero outside."""
    H, W = x.shape
    pad = np.zeros((H + 2, W + 2), x.dtype)
    pad[1:1 + H, 1:1 + W] = x
    return pad[1 + dy:1 + dy + H, 1 + dx:1 + dx + W]


def _dilate3(x):
    rows = x | _shift(x, 0, -1) | _shift(x, 0, 1)
    return rows | _shift(rows, -1, 0) | _shift(rows, 1, 0)


def edges(frame: np.ndarray, *, control: bool = False) -> np.ndarray:
    """(H, W) bool Canny edge map of a uint8 frame."""
    gx, gy = gradients(frame, control=control)
    f = np.float32
    ax, ay = np.abs(gx), np.abs(gy)
    mag = _sum_squares(gx, gy)
    d0 = f(128) * ay < f(53) * ax
    d90 = f(128) * ay >= f(309) * ax
    diag = ~(d0 | d90)
    same_sign = (gx >= 0) == (gy >= 0)
    dirs = np.where(d0, 0, np.where(d90, 2, np.where(same_sign & diag, 1, 3)))
    H, W = mag.shape
    inside = np.zeros((H, W), bool)
    inside[BORDER:H - BORDER, BORDER:W - BORDER] = True
    mag = np.where(inside, mag, f(0))
    pairs = [((0, 1), (0, -1)), ((-1, 1), (1, -1)),
             ((1, 0), (-1, 0)), ((1, 1), (-1, -1))]
    keep = np.zeros((H, W), bool)
    for b, (p, q) in enumerate(pairs):
        keep |= (dirs == b) & (mag >= _shift(mag, *p)) & (mag >= _shift(mag, *q))
    sup = np.where(keep, mag, f(0))
    strong = sup >= HIGH2
    weak = (sup >= LOW2) & ~strong
    s = strong
    for _ in range(HYSTERESIS_ITERS):
        s = s | (weak & _dilate3(s))
    return s


def max_votes_per_frame(h: int, w: int) -> int:
    """The largest compaction buffer: edges past it are not voted."""
    return max(256, (h * w) // 16)


def voters(edge_map: np.ndarray, corridors: np.ndarray | None = None
           ) -> np.ndarray:
    """Flat raster indices of the pixels that vote: edges inside some
    corridor (when given), the first ``max_votes_per_frame`` of them."""
    H, W = edge_map.shape
    idx = np.flatnonzero(edge_map)
    if corridors is not None:
        idx = idx[corridor_keep(idx, W, corridors)]
    return idx[:max_votes_per_frame(H, W)]


def corridor_keep(idx: np.ndarray, width: int, corridors: np.ndarray
                  ) -> np.ndarray:
    f = np.float32
    cor = np.asarray(corridors, np.float32)
    normal = np.floor(cor[:, :2] * f(8192) + f(0.5)) * f(1 / 8192)
    x = (idx % width).astype(np.float32)[:, None]
    y = (idx // width).astype(np.float32)[:, None]
    rho = x * normal[:, 0] + y * normal[:, 1]
    return ((rho >= cor[:, 2]) & (rho <= cor[:, 3])).any(axis=1)


def trig_table(h: int, w: int) -> np.ndarray:
    """(3, 180) f32 rows cos, sin and the diagonal shift."""
    theta = np.arange(N_THETA, dtype=np.float32) * np.float32(math.pi / N_THETA)
    diag = np.float32(math.hypot(h, w))
    return np.stack([np.cos(theta), np.sin(theta),
                     np.full_like(theta, diag)]).astype(np.float32)


def n_rho_bins(h: int, w: int) -> int:
    return int(2.0 * math.hypot(h, w)) + 1


def vote(idx: np.ndarray, h: int, w: int,
         theta_bins: np.ndarray | None = None) -> np.ndarray:
    """(n_rho, T) accumulator over the voting pixels ``idx``; T = 180, or
    the gate's length with its columns in the gate's order."""
    trig = trig_table(h, w)
    if theta_bins is not None:
        trig = trig[:, np.asarray(theta_bins)]
    T = trig.shape[1]
    x = (idx % w).astype(np.float32)[:, None]
    y = (idx // w).astype(np.float32)[:, None]
    rho = x * trig[0]
    rho = rho + y * trig[1]
    rho = rho + trig[2]
    r = np.floor(rho).astype(np.int64)
    n_rho = n_rho_bins(h, w)
    flat = (r * T + np.arange(T)[None, :]).ravel()
    return np.bincount(flat, minlength=n_rho * T).reshape(n_rho, T).astype(
        np.float32)


def _maxpool(v: np.ndarray, k: int) -> np.ndarray:
    p = k // 2
    R, T = v.shape
    pad = np.full((R + 2 * p, T + 2 * p), -np.inf, np.float32)
    pad[p:p + R, p:p + T] = v
    rows = np.max(np.lib.stride_tricks.sliding_window_view(pad, k, axis=0),
                  axis=-1)
    return np.max(np.lib.stride_tricks.sliding_window_view(rows, k, axis=1),
                  axis=-1)


def peaks(votes: np.ndarray, h: int, w: int,
          theta_bins: np.ndarray | None = None) -> list[tuple[int, int]]:
    """Valid peaks as (rho bin, theta bin) pairs, in rank order."""
    diag = math.hypot(h, w)
    thresh = max(np.float32(MIN_VOTES_FRAC * diag),
                 np.float32(0.5) * votes.max())
    is_peak = (votes >= thresh) & (votes >= _maxpool(votes, NEIGHBORHOOD))
    score = np.where(is_peak, votes, np.float32(-1)).ravel()
    order = np.argsort(-score, kind="stable")[:MAX_LINES]
    T = votes.shape[1]
    out = []
    for i in order:
        if score[i] > 0:
            col = int(i % T)
            t = int(theta_bins[col]) if theta_bins is not None else col
            out.append((int(i // T), t))
    return out


def answer(edge_map: np.ndarray, theta_bins: np.ndarray | None = None,
           corridors: np.ndarray | None = None) -> list[tuple[int, int]]:
    """The valid peaks of an edge map under a dispatch's gate and
    corridors (None: the full sweep, no corridor filter)."""
    h, w = edge_map.shape
    idx = voters(edge_map, corridors)
    return peaks(vote(idx, h, w, theta_bins), h, w, theta_bins)


def detect(frame: np.ndarray, theta_bins: np.ndarray | None = None,
           corridors: np.ndarray | None = None, *, control: bool = False
           ) -> list[tuple[int, int]]:
    """The valid peaks of one uint8 frame."""
    return answer(edges(frame, control=control), theta_bins, corridors)
