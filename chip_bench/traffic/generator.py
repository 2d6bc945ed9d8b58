"""The one traffic generator: turns a mix file and a seed into frames and
a send schedule.  Every mix is data read here; a new mix is a new file.

Kinds of mix (the ``kind`` key):

* ``open_streams``: ``streams`` camera streams, each one session at the
  configuration's camera rate.  Every seed offers the same arrivals and
  the same work: stream ``s``'s first frame is due at its ``phase`` (a
  fraction of a frame period), frames follow at the exact period, and
  each stream drives through all of ``families`` in turn,
  ``segment_frames`` frames on each, playing that family's standard drive
  cycle (``cycle_frames`` frames) back and forth.  Streams start at
  evenly spread places in the round, so at any time they show different
  families.  The seed draws the scenes (lane angles, texture, noise), not
  which families meet in a batch.  Frames due are sent whatever the
  service is doing (an open loop).
* ``closed_loop``: ``in_flight`` sessionless frames are kept outstanding;
  each answer sends the next frame.  Frames come from a pool of
  ``pool_frames`` scenes spread evenly over ``families`` (scene seeds
  drawn by seed), in a seeded order drawn anew for each pass.

``families`` is ``"marked"`` (the eleven families with lane markings),
``"all"`` (the twelve, ``empty`` included) or a list of family names.
``phase`` (open streams; default ``"spread"``) is ``"spread"`` (stream
``s`` of ``n`` at ``s / n``), ``"synced"`` (every stream at 0: triggers
aligned across cameras) or a list of fractions, used by the streams in
turn.  ``warm_s`` seconds of the same traffic run in set-up before the
window.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import scenes

N_ORDERS = 64       # closed loop: seeded pool orders, used in turn


FAMILY_SETS = {"marked": scenes.MARKED_FAMILIES,
               "all": tuple(scenes.FAMILIES)}


def families(which) -> tuple[str, ...]:
    """A named family set or a list of family names."""
    if isinstance(which, str):
        if which not in FAMILY_SETS:
            raise ValueError(f"unknown family set {which!r}; known: "
                             f"{sorted(FAMILY_SETS)} or a list of names")
        return FAMILY_SETS[which]
    unknown = [f for f in which if f not in scenes.FAMILIES]
    if unknown or not which:
        raise ValueError(f"unknown families {unknown}; known: "
                         f"{list(scenes.FAMILIES)}")
    return tuple(which)


def phases(phase, n: int) -> list[float]:
    """Frame-0 offsets of ``n`` streams, in frame periods."""
    if phase == "spread":
        return [s / n for s in range(n)]
    if phase == "synced":
        return [0.0] * n
    if isinstance(phase, list) and phase:
        return [float(phase[s % len(phase)]) for s in range(n)]
    raise ValueError(f"unknown phase {phase!r}: \"spread\", \"synced\" "
                     f"or a list of fractions of a period")


def bounce(k: int, n: int) -> int:
    """Position ``k`` of a cycle of ``n`` frames played back and forth."""
    if n == 1:
        return 0
    k %= 2 * (n - 1)
    return k if k < n else 2 * (n - 1) - k


@dataclasses.dataclass
class Stream:
    session: str
    order: list           # families, in the order this stream drives them
    cycles: dict          # family -> drive cycle, uint8 (H, W) frames
    segment_frames: int
    phase_s: float        # due time of frame 0 after the traffic starts
    period_s: float

    def frame(self, k: int) -> tuple[tuple, np.ndarray]:
        """(key, frame) of the stream's k-th frame."""
        fam = self.order[(k // self.segment_frames) % len(self.order)]
        cycle = self.cycles[fam]
        i = bounce(k % self.segment_frames, len(cycle))
        return (fam, i), cycle[i]

    def due(self, k: int) -> float:
        return self.phase_s + k * self.period_s


@dataclasses.dataclass
class Traffic:
    kind: str
    height: int
    width: int
    deadline_s: Optional[float]
    warm_s: float
    streams: list = dataclasses.field(default_factory=list)
    pool: list = dataclasses.field(default_factory=list)
    orders: list = dataclasses.field(default_factory=list)
    in_flight: int = 0

    def pool_frame(self, i: int) -> tuple[tuple, np.ndarray]:
        """(key, frame) of the closed loop's i-th send."""
        n = len(self.pool)
        j = int(self.orders[(i // n) % len(self.orders)][i % n])
        return ("pool", j), self.pool[j]


def build(config: dict, mix: dict, seed: int, *,
          streams: Optional[int] = None) -> Traffic:
    """Frames and schedule for one run.  ``streams`` overrides the mix's
    stream count (the knee sweep)."""
    rng = np.random.default_rng(seed)
    h, w = config["frame"]["height"], config["frame"]["width"]
    kind = mix["kind"]
    fams = families(mix["families"])
    if kind == "open_streams":
        n = mix["streams"] if streams is None else streams
        period = 1.0 / config["fps"]
        order = list(fams)
        offsets = phases(mix.get("phase", "spread"), n)
        cycles = {f: scenes.drive_cycle(f, mix["cycle_frames"], h, w,
                                        int(rng.integers(0, 2**31)))
                  for f in order}
        out = [Stream(session=f"cam{s}",
                      order=order[s * len(order) // n:]
                      + order[:s * len(order) // n],
                      cycles=cycles, segment_frames=mix["segment_frames"],
                      phase_s=offsets[s] * period, period_s=period)
               for s in range(n)]
        return Traffic(kind, h, w, config["deadline_ms"] / 1e3,
                       mix["warm_s"], streams=out)
    if kind == "closed_loop":
        pool = [scenes.scene(fams[i % len(fams)], h, w,
                             int(rng.integers(0, 2**31)))
                for i in range(mix["pool_frames"])]
        orders = [rng.permutation(len(pool)) for _ in range(N_ORDERS)]
        return Traffic(kind, h, w, None, mix["warm_s"], pool=pool,
                       orders=orders, in_flight=mix["in_flight"])
    raise ValueError(f"unknown traffic kind {kind!r}")
