"""The plain reference against the program's detection on the CPU, at a
small size: the full sweep, a theta gate, and a fused dispatch with
corridors give the same valid peaks; the bfloat16 control does not."""

import math

import numpy as np
import pytest

from chip_bench.reference import lanes
from chip_bench.traffic import scenes

H, W = 96, 128


@pytest.fixture(scope="module")
def plan():
    from repro.core import HoughConfig, PipelineConfig
    from repro.core.plan import DetectionPlan

    cfg = PipelineConfig(hough=HoughConfig(compact=True, max_edges="auto"))
    return DetectionPlan.build(cfg, H, W, batch=4)


def program_peaks(res, i):
    pk = np.asarray(res.peaks[i], np.float64)[np.asarray(res.valid[i], bool)]
    diag = math.hypot(H, W)
    return sorted((int(round(r + diag)), int(round(t * 180 / math.pi)))
                  for r, t in pk)


GATE = np.asarray(list(range(20, 50)) + [20] * 10, np.int32)
CORRIDORS = np.asarray(
    [[math.cos(t), math.sin(t), r - 12.0, r + 12.0]
     for r, t in ((50.0, 0.6), (80.0, 2.4), (30.0, 1.2), (60.0, 2.0))],
    np.float32)


@pytest.mark.parametrize("mode", ["full", "gated", "fused"])
def test_reference_matches_the_program(plan, mode):
    fams = list(scenes.FAMILIES)
    frames = [scenes.scene(fams[i], H, W, 40 + i) for i in range(4)]
    p, bins, cors = plan, None, None
    if mode != "full":
        p, bins = plan.with_theta_band(len(GATE)), GATE
    if mode == "fused":
        p, cors = p.with_fused(len(CORRIDORS)), CORRIDORS
    res = p.run(np.stack(frames).astype(np.float32), bins, cors)
    for i, f in enumerate(frames):
        assert program_peaks(res, i) == sorted(lanes.detect(f, bins, cors))


def test_control_changes_answers():
    frames = [scenes.scene(f, H, W, 7) for f in scenes.MARKED_FAMILIES]
    differ = sum(sorted(lanes.detect(f)) != sorted(lanes.detect(
        f, control=True)) for f in frames)
    assert differ >= 2


def test_vote_keeps_at_most_the_largest_buffer():
    edge_map = np.ones((H, W), bool)
    assert len(lanes.voters(edge_map)) == lanes.max_votes_per_frame(H, W)
    assert lanes.max_votes_per_frame(H, W) == max(256, H * W // 16)
