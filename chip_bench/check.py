"""The comparison that decides ``correct``.

Once the window has closed and every frame has its answer, a sample of
the frames the window answered in full, drawn from the seed, goes through
the plain reference (``reference/lanes.py``) with the gate and corridors
its dispatch actually shipped.  The numbers compared, each with its limit:

* ``peak_mismatch_pct``: the share of sampled frames whose valid peaks,
  as (rho bin, theta bin) pairs, differ from the reference's: the answer,
  through every layer (Canny, corridors, edge count and compaction, the
  vote, the peak search, the gate).
* ``edge_mismatch_px``: Canny edge pixels that differ from the
  reference's, summed over the sampled frames of staged dispatches (a
  fused dispatch returns no edge map).  The gradients are exact integer
  sums, so the limit is 0.
* ``unanswered``: frames that never reached a terminal status, limit 0.
* ``compared``: sampled frames, at least ``MIN_COMPARED``.

PERF.md gives the readings each limit was set from: the program's over
many seeds, and the control's (the reference with the Gaussian's output
rounded to bfloat16, put in the program's place).
"""

from __future__ import annotations

import math

import numpy as np

from chip_bench.reference import lanes

N_SAMPLE = 64
MIN_COMPARED = 16
LIMITS = {"peak_mismatch_pct": 2.0, "edge_mismatch_px": 0, "unanswered": 0}


def peaks_of(req, h: int, w: int) -> list[tuple[int, int]]:
    """The program's valid peaks as (rho bin, theta bin) pairs."""
    res = req.result
    pk = np.asarray(res.peaks, np.float64)[np.asarray(res.valid, bool)]
    diag = math.hypot(h, w)
    return sorted((int(round(r + diag)), int(round(t * 180.0 / math.pi)))
                  for r, t in pk)


def compare(sent, by_uid: dict, seed: int, *, control: bool = False,
            n_sample: int = N_SAMPLE) -> dict:
    """Compare a seeded sample of ``sent`` (frames answered DONE) with the
    reference.  With ``control``, also the control against the reference
    on the same frames."""
    done = [r for r in sent if r.req.ok and r.uid in by_uid]
    rng = np.random.default_rng([seed % 2**63, 17])
    pick = rng.choice(len(done), size=min(n_sample, len(done)),
                      replace=False) if done else []
    bad = bad_ctl = px = px_ctl = 0
    for j in sorted(pick):
        r = done[j]
        d = by_uid[r.uid]
        h, w = r.frame.shape
        ref_edges = lanes.edges(r.frame)
        want = sorted(lanes.answer(ref_edges, d.bins, d.cors))
        bad += peaks_of(r.req, h, w) != want
        if d.cors is None:
            got = np.asarray(r.req.result.edges) > 0
            px += int((got != ref_edges).sum())
        if control:
            ctl_edges = lanes.edges(r.frame, control=True)
            bad_ctl += sorted(lanes.answer(ctl_edges, d.bins, d.cors)) != want
            if d.cors is None:
                px_ctl += int((ctl_edges != ref_edges).sum())
    n = len(pick)
    out = {"compared": n, "edge_mismatch_px": px,
           "peak_mismatch_pct": 100.0 * bad / n if n else 100.0}
    if control:
        out["control"] = {"peak_mismatch_pct": 100.0 * bad_ctl / n
                          if n else 100.0, "edge_mismatch_px": px_ctl}
    return out


def verdict(readings: dict, unanswered: int) -> tuple[bool, dict]:
    """``correct`` and the numbers compared beside their limits."""
    values = {"peak_mismatch_pct": readings["peak_mismatch_pct"],
              "edge_mismatch_px": readings["edge_mismatch_px"],
              "unanswered": unanswered}
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    checks["compared"] = {"value": readings["compared"],
                          "limit": MIN_COMPARED}
    ok = (all(v <= LIMITS[k] for k, v in values.items())
          and readings["compared"] >= MIN_COMPARED)
    return ok, checks
