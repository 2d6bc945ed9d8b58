"""Frozen copy of the road-scene frame generators the cells send.

A copy, not an import: the program's own generators (``repro.data``) may
change in a later change, and the frames a cell sends are part of the
yardstick.  Taken from ``src/repro/data/scenarios.py`` and
``src/repro/data/images.py``: the twelve scenario families and the
standard drive cycle (sway, a curvature ramp, a mid-cycle lane change,
and on the noisy families a 3-frame dropout and a 4-frame noise burst).
Only the images are kept; the ground truth the program's scorer needs is
not, because the benchmark compares answers with its own reference.

Every function is a pure function of its arguments: the same family,
shape and seed give the same uint8 frames.
"""

from __future__ import annotations

import math
from typing import Callable

import numpy as np

#: Families whose drive cycle carries a dropout and a noise burst.
NOISY_FAMILIES = ("rain", "night", "glare")


def _asphalt(h, w, rng, *, level=90.0, noise=4.0):
    img = np.full((h, w), level, np.float32)
    img += rng.normal(0.0, noise, img.shape).astype(np.float32)
    return img


def _draw_segment(img, p0, p1, intensity, width=1.6):
    """Paint pixels within ``width`` of the segment p0-p1 (clamped ends)."""
    H, W = img.shape
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    dx, dy = p1[0] - p0[0], p1[1] - p0[1]
    norm2 = dx * dx + dy * dy + 1e-9
    t = np.clip(((xx - p0[0]) * dx + (yy - p0[1]) * dy) / norm2, 0.0, 1.0)
    dist = np.hypot(xx - (p0[0] + t * dx), yy - (p0[1] + t * dy))
    img[dist <= width] = intensity


def _draw_line(img, rho, theta, intensity, width):
    H, W = img.shape
    yy, xx = np.mgrid[0:H, 0:W]
    dist = np.abs(xx * math.cos(theta) + yy * math.sin(theta) - rho)
    img[dist <= width] = intensity


def _finish(img):
    return np.clip(img, 0, 255).astype(np.uint8)


def _walk_up(p0, theta_deg, y_stop):
    theta = math.radians(theta_deg)
    dx, dy = math.sin(theta), -math.cos(theta)
    if dy > 0:
        dx, dy = -dx, -dy
    span = (p0[1] - y_stop) / max(-dy, 1e-6)
    return p0[0] + span * dx, p0[1] + span * dy


def _lane(h, w, x_bottom_frac, theta_deg, *, y_top_frac=0.05,
          y_bottom_frac=0.98):
    p0 = (x_bottom_frac * w, y_bottom_frac * h)
    return p0, _walk_up(p0, theta_deg, y_top_frac * h)


def _road(h, w, seed, *, dashed=False):
    """The seed workload: two converging lane lines."""
    rng = np.random.default_rng(seed)
    img = np.full((h, w), 90, np.float32)
    img += rng.normal(0.0, 4.0, img.shape)
    for fx, deg in ((0.35, 55.0), (0.65, 125.0)):
        theta = math.radians(deg + rng.uniform(-4, 4))
        rho = fx * w * math.cos(theta) + 0.75 * h * math.sin(theta)
        _draw_line(img, rho, theta, 235, 1.6)
    if dashed:
        mask = (np.arange(h)[:, None] // 12) % 2 == 0
        img = np.where(mask & (img > 200), 90.0, img)
    return np.clip(img, 0, 255).astype(np.uint8)


def _straight(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _asphalt(h, w, rng)
    for fx, deg in ((0.30, 8.0), (0.70, 172.0)):
        _draw_segment(img, *_lane(h, w, fx + rng.uniform(-0.02, 0.02),
                                  deg + rng.uniform(-2.0, 2.0)), 235.0)
    return _finish(img)


def _curved(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _asphalt(h, w, rng)
    for fx, deg, bend in ((0.30, 22.0, -12.0), (0.70, 158.0, 12.0)):
        deg += rng.uniform(-2.0, 2.0)
        p0 = (fx * w, 0.98 * h)
        pm = _walk_up(p0, deg, 0.50 * h)
        _draw_segment(img, p0, pm, 235.0)
        _draw_segment(img, pm, _walk_up(pm, deg + bend, 0.10 * h), 235.0)
    return _finish(img)


def _night(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _asphalt(h, w, rng, level=42.0, noise=5.0)
    for fx, deg in ((0.35, 35.0), (0.65, 145.0)):
        _draw_segment(img, *_lane(h, w, fx, deg + rng.uniform(-3.0, 3.0),
                                  y_bottom_frac=0.9, y_top_frac=0.1), 130.0)
    return _finish(img)


def _two_lanes(h, w, rng, degs=((0.35, 35.0), (0.65, 145.0)), jitter=3.0,
               **kw):
    img = _asphalt(h, w, rng)
    for fx, deg in degs:
        _draw_segment(img, *_lane(h, w, fx, deg + rng.uniform(-jitter,
                                                                jitter), **kw),
                      235.0)
    return img


def _glare(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _two_lanes(h, w, rng)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    for _ in range(3):
        cx = rng.uniform(0.15, 0.85) * w
        cy = rng.uniform(0.05, 0.4) * h
        r = rng.uniform(0.03, 0.07) * min(h, w)
        blob = 165.0 * np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2)
                              / (2.0 * r * r))
        img = np.minimum(img + blob, 255.0)
    return _finish(img)


def _rain(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _two_lanes(h, w, rng)
    speck = rng.uniform(size=img.shape)
    img[speck < 0.004] = 255.0
    img[speck > 0.996] = 0.0
    return _finish(img)


def _occlusion(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _two_lanes(h, w, rng)
    x0 = int(rng.uniform(0.3, 0.45) * w)
    y0 = int(rng.uniform(0.35, 0.5) * h)
    ow, oh = int(0.18 * w), int(0.14 * h)
    img[y0:y0 + oh, x0:x0 + ow] = 108.0 + rng.normal(
        0.0, 3.0, (min(oh, h - y0), min(ow, w - x0))).astype(np.float32)
    return _finish(img)


def _multilane(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _asphalt(h, w, rng)
    vx = (0.5 + rng.uniform(-0.03, 0.03)) * w
    vy = 0.04 * h
    for fx in (0.18, 0.40, 0.60, 0.82):
        x0, y0 = fx * w, 0.98 * h
        t = (0.32 * h - y0) / (vy - y0)
        _draw_segment(img, (x0, y0), (x0 + t * (vx - x0), y0 + t * (vy - y0)),
                      235.0)
    return _finish(img)


def _fog(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _two_lanes(h, w, rng, ((0.35, 30.0), (0.65, 150.0)),
                     y_top_frac=0.12)
    beta = rng.uniform(1.1, 1.5)
    depth = np.linspace(1.0, 0.0, h, dtype=np.float32)[:, None]
    t = np.exp(-beta * depth)
    return _finish(img * t + 190.0 * (1.0 - t))


def _lens_distortion(h, w, seed):
    rng = np.random.default_rng(seed)
    img = _two_lanes(h, w, rng, ((0.32, 25.0), (0.68, 155.0)), jitter=2.0)
    k1 = rng.uniform(0.010, 0.018)
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    dx, dy = xx - cx, yy - cy
    scale = 1.0 + k1 * (np.hypot(dx, dy) / math.hypot(cx, cy)) ** 2
    sx = np.clip(np.rint(cx + dx * scale), 0, w - 1).astype(np.int32)
    sy = np.clip(np.rint(cy + dy * scale), 0, h - 1).astype(np.int32)
    return _finish(img[sy, sx])


def _empty(h, w, seed):
    return _finish(_asphalt(h, w, np.random.default_rng(seed)))


#: The twelve families in the program's registry order; all but ``empty``
#: carry lane markings.
FAMILIES: dict[str, Callable[[int, int, int], np.ndarray]] = {
    "straight": _straight,
    "converging": lambda h, w, seed: _road(h, w, seed),
    "dashed": lambda h, w, seed: _road(h, w, seed, dashed=True),
    "curved": _curved,
    "night": _night,
    "glare": _glare,
    "rain": _rain,
    "occlusion": _occlusion,
    "multilane": _multilane,
    "fog": _fog,
    "lens_distortion": _lens_distortion,
    "empty": _empty,
}
MARKED_FAMILIES = tuple(f for f in FAMILIES if f != "empty")


def scene(family: str, h: int, w: int, seed: int) -> np.ndarray:
    """One (h, w) uint8 frame of ``family``."""
    return FAMILIES[family](h, w, seed)


def _smoothstep(u):
    u = min(max(u, 0.0), 1.0)
    return u * u * (3.0 - 2.0 * u)


def _warp_rigid(img, *, yaw_rad, dx, dy, fill):
    H, W = img.shape
    cy, cx = (H - 1) / 2.0, (W - 1) / 2.0
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    qx, qy = xx - cx - dx, yy - cy - dy
    c, s = math.cos(yaw_rad), math.sin(yaw_rad)
    sx = np.rint(c * qx + s * qy + cx).astype(np.int64)
    sy = np.rint(-s * qx + c * qy + cy).astype(np.int64)
    inside = (sx >= 0) & (sx < W) & (sy >= 0) & (sy < H)
    out = np.full((H, W), np.uint8(np.clip(round(fill), 0, 255)))
    out[inside] = img[sy[inside], sx[inside]]
    return out


def drive_cycle(family: str, n_frames: int, h: int, w: int, seed: int
                ) -> list[np.ndarray]:
    """The standard drive cycle of ``family``: ``n_frames`` uint8 frames of
    one base scene seen through a swaying, yawing camera that changes lane
    mid-cycle, with a dropout and a noise burst on the noisy families."""
    base = scene(family, h, w, seed)
    fill = float(np.median(base))
    noisy = family in NOISY_FAMILIES
    third = n_frames // 3
    dropouts = set(range(third, third + 3)) if noisy else set()
    bursts = set(range(2 * third, 2 * third + 4)) if noisy else set()
    change_at, change_len = n_frames // 2, max(12, n_frames // 2)
    span = max(n_frames - 1, 1)
    frames = []
    for t in range(n_frames):
        dx = 5.0 * math.sin(2.0 * math.pi * t / 32.0)
        dx += 0.12 * w * _smoothstep(
            (t - (change_at - change_len / 2.0)) / change_len)
        yaw = math.radians(2.5) * math.sin(math.pi * t / span)
        if t in dropouts:
            rng = np.random.default_rng([seed, 7_000_000 + t])
            img = np.clip(rng.normal(10.0, 3.0, (h, w)), 0, 255
                          ).astype(np.uint8)
        else:
            img = _warp_rigid(base, yaw_rad=yaw, dx=dx, dy=0.0, fill=fill)
            if t in bursts:
                rng = np.random.default_rng([seed, 9_000_000 + t])
                speck = rng.uniform(size=img.shape)
                img = img.copy()
                img[speck < 0.012] = 255
                img[speck > 1.0 - 0.012] = 0
        frames.append(img)
    return frames
