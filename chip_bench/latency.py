"""End-to-end arithmetic over the frames of one window.

A frame is *attempted* when it is due in the window (open loop) or sent in
it (closed loop).  Its latency runs from its due time to its terminal
answer.  A frame with no answer (refused, shed, failed, or never
terminal) ranks above every answered frame; where such a frame sits at a
percentile, the percentile reads the time from its due time to the end of
the run.  Percentiles are nearest-rank over all attempted frames, never
medians of chunks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence


@dataclasses.dataclass
class Frame:
    due: float                     # when it was due (host clock, s)
    sent: float                    # when submit was called
    answered: Optional[float]      # terminal answer time; None: no answer
    done: bool                     # answered in full (DONE)
    deadline: Optional[float]      # absolute deadline, None if none


def nearest_rank(n: int, q: float) -> int:
    """0-based index of the ``q``-th percentile among ``n`` sorted values."""
    return min(n - 1, max(0, math.ceil(q / 100.0 * n) - 1))


def percentile_s(frames: Sequence[Frame], q: float, end: float) -> float:
    """The ``q``-th latency percentile, unanswered frames ranked last."""
    answered = sorted(f.answered - f.due for f in frames
                      if f.answered is not None)
    missing = sorted(end - f.due for f in frames if f.answered is None)
    ranked = answered + missing
    return ranked[nearest_rank(len(ranked), q)]


def failed(frames: Sequence[Frame]) -> int:
    """Frames not answered in full by their deadline."""
    return sum(1 for f in frames
               if not f.done or (f.deadline is not None
                                 and f.answered > f.deadline))


def goodput_fps(frames: Sequence[Frame], t0: float, t1: float) -> float:
    """Frames answered in full inside ``[t0, t1)``, per second of it."""
    n = sum(1 for f in frames
            if f.done and f.answered is not None and t0 <= f.answered < t1)
    return n / (t1 - t0)


def percentile_ms(q: float):
    """Reader of the ``q``-th latency percentile of a run record, ms."""
    def read(run):
        return 1e3 * percentile_s(run["frames"], q, run["t_end"])
    return read


def window_goodput_fps(run) -> float:
    """Reader of ``goodput_fps`` over every frame the run sent."""
    return goodput_fps(run["all_frames"], run["t0"], run["t1"])
