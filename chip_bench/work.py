"""The work the algorithm needs, counted from shapes and from the frames,
whatever implements it: the numerators of the kernels' roofline shares.

* Canny (the Gaussian and the Sobel pair, one layer whether the staged
  convolution kernel or the fused kernel A runs it): 25 + 2 * 9 = 43
  multiply-adds per pixel, 2 operations each; one byte per pixel in (the
  uint8 camera frame) and one byte per pixel out (an edge map).
* Vote: for each voting pixel (an edge inside the dispatch's corridors,
  as the reference finds them, at most the largest compaction buffer)
  and each theta bin the dispatch swept (distinct gate bins, or 180), two
  multiply-adds for ``rho``: 4 operations; bytes are the accumulator
  (rho bins x swept bins x 4) plus one 4-byte index per voting pixel.

Empty slots of a dispatch carry no frame and count nothing.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from chip_bench.reference import lanes

CANNY_MACS_PER_PIXEL = 25 + 2 * 9


def peaks_for(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def count(dispatches, sent_by_uid: dict) -> dict:
    """``{"canny": {...}, "hough_vote": {...}}`` flops and bytes over the
    real frames of ``dispatches``."""
    edge_cache: dict = {}
    canny = {"flops": 0.0, "bytes": 0.0, "frames": 0}
    vote = {"flops": 0.0, "bytes": 0.0, "voters": 0}
    for d in dispatches:
        for uid in d.uids:
            rec = sent_by_uid.get(uid)
            if rec is None:
                continue
            h, w = rec.frame.shape
            canny["flops"] += 2.0 * CANNY_MACS_PER_PIXEL * h * w
            canny["bytes"] += 2.0 * h * w
            canny["frames"] += 1
            if rec.key not in edge_cache:
                edge_cache[rec.key] = lanes.edges(rec.frame)
            e = len(lanes.voters(edge_cache[rec.key], d.cors))
            t = (len(np.unique(d.bins)) if d.bins is not None
                 else lanes.N_THETA)
            vote["flops"] += 4.0 * e * t
            vote["bytes"] += 4.0 * lanes.n_rho_bins(h, w) * t + 4.0 * e
            vote["voters"] += e
    return {"canny": canny, "hough_vote": vote}


def roofline_pct(work: dict, seconds: float, peak: dict) -> float | None:
    """Least time the chip could take over the time it took, in %."""
    if not seconds or not work or not work.get("flops"):
        return None
    t_min = max(work["flops"] / peak["flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * t_min / seconds
