"""Hough transform (paper Section 4.2 / Algorithm 2) in GEMM + histogram form.

The paper keeps this stage on the scalar core: its voting loop is a chain of
data-dependent read-modify-writes (CPI > 3 on both Rocket and BOOM, Table 6)
and Gemmini buys it nothing (Table 7).  The TPU adaptation dissolves the
dependency — see ``kernels/hough_vote.py``.  This module provides:

  * ``hough_transform``   — the accelerated path: homogeneous-coordinate rho
    GEMM + blockwise one-hot vote accumulation.
  * ``hough_paper_loop``  — a faithful scalar-form reference implementing
    Algorithm 2's per-pixel/per-theta loop nest (``lax`` loops, one pixel at
    a time).  This is the measured "no-accelerator baseline" in the
    benchmarks, the analogue of the paper's Rocket/BOOM-only runs.

``hough_transform`` accepts batches (N, H, W) — one batched vote kernel —
and ``HoughConfig(compact=True, max_edges=...)`` enables the edge-compaction
pre-pass (vote over <=max_edges compacted edge pixels instead of H*W; exact
same accumulator as long as the buffer isn't exceeded).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class HoughConfig:
    n_theta: int = 180          # 1-degree bins, theta in [0, 180)
    rho_res: float = 1.0        # rho bin width (pixels)
    edge_threshold: float = 250.0  # paper: image[i*width+j] >= 250
    impl: str | None = None
    # Edge-compaction fast path: prefix-sum-scatter the (typically <5%)
    # edge pixels into a static buffer so the vote stage iterates
    # ``max_edges`` pixels instead of H*W.  ``max_edges=None`` defers to
    # the dispatch default in ``kernels.ops.hough_vote`` (~H*W/16); edges
    # beyond the buffer are dropped, so leave compaction off when exact
    # parity on pathologically dense edge maps matters.  ``max_edges="auto"``
    # sizes the buffer from the workload itself: the plan path counts the
    # edge map ON DEVICE and ``lax.switch``-es over the static tier set
    # (``hough_transform_tiered`` — zero host syncs, jit-safe); the eager
    # ``hough_transform`` counts the concrete edge map and the legacy
    # resolver estimates from a downsampled gradient pass
    # (``canny.estimate_edge_count``) — all land on a tier via
    # ``auto_max_edges`` that never exceeds the dense default.
    compact: bool = False
    max_edges: int | str | None = None
    # Prediction-gated voting (core/tracking.py): when set, the vote stage
    # sweeps only ``theta_band`` theta bins — a runtime int32 vector of bin
    # indices (the tracker's union of windows around predicted lanes,
    # padded to this static length) gathers the trig columns, and the band
    # scatters back into the full-width accumulator (zeros outside the
    # gate) so get_lines and every consumer keep full-sweep indexing.  The
    # *length* is static (a plan attribute — one compiled program per
    # band), the bin values are data (the gate slides every frame without
    # recompiling).  None = full sweep.
    theta_band: int | None = None
    # Rho-corridor edge pre-filter (the fused hot path only): when set, the
    # fused detect kernel drops edge pixels outside every one of
    # ``corridors`` per-track rho windows before compaction/voting —
    # cutting the vote's *pixel* axis the way ``theta_band`` cuts its theta
    # axis.  Like the band, the corridor *count* is static (plan attribute)
    # while the window values (``[cos, sin, rho_lo, rho_hi]`` rows from
    # ``tracking.LaneTracker.corridors``) are runtime data.  None = no
    # filtering.  ``full_corridors`` builds pass-everything windows, under
    # which the fused path is bit-exact with the staged full sweep.
    corridors: int | None = None


# Corridor windows wider than any image diagonal: a (lo, hi) of
# (-CORRIDOR_INF, CORRIDOR_INF) passes every pixel.
CORRIDOR_INF = 1e9


def full_corridors(n: int = 1) -> np.ndarray:
    """(n, 4) corridor rows that pass every pixel (full-coverage fallback).

    Every row is the same all-pass window, so padding a real corridor set
    with these (or using them outright on cold start) is idempotent under
    the kernel's any-corridor OR.
    """
    row = np.array([1.0, 0.0, -CORRIDOR_INF, CORRIDOR_INF], np.float32)
    return np.tile(row, (n, 1))


def rho_bins(height: int, width: int, cfg: HoughConfig) -> int:
    diag = math.hypot(height, width)
    return int(2.0 * diag / cfg.rho_res) + 1


def hough_trig(height: int, width: int, cfg: HoughConfig) -> np.ndarray:
    """(3, n_theta) homogeneous trig table for the rho GEMM.

    Rows ``cos/rho_res``, ``sin/rho_res``, and the folded ``+diag`` shift —
    so ``floor(xy_homogeneous @ trig)`` is directly the rho bin index.
    Shared by the staged vote (``_hough_transform``) and the fused hot
    path's kernel B so both bin identically.
    """
    diag = math.hypot(height, width)
    theta = np.arange(cfg.n_theta, dtype=np.float32) * (
        math.pi / cfg.n_theta
    )
    return np.stack(
        [
            np.cos(theta) / cfg.rho_res,
            np.sin(theta) / cfg.rho_res,
            np.full_like(theta, diag / cfg.rho_res),
        ]
    ).astype(np.float32)


def max_edge_tiers(height: int, width: int, *, base: int = 512
                   ) -> tuple[int, ...]:
    """The static set of compaction-buffer sizes for one resolution.

    Geometric tiers ``base, 2*base, 4*base, ...`` capped at (and always
    including) the dense-dispatch default (``kernels.ops.default_max_edges``)
    — a small finite set, so everything keyed on a resolved ``max_edges``
    (jit cache entries, the tiered ``lax.switch`` in the plan path) stays
    bounded no matter how edge density drifts across a stream.
    """
    cap = ops.default_max_edges(height * width)
    tiers = []
    t = base
    while t < cap:
        tiers.append(t)
        t *= 2
    tiers.append(cap)
    return tuple(tiers)


def auto_max_edges(n_edges: int, height: int, width: int, *,
                   base: int = 512) -> int:
    """Tiered compaction-buffer size for an (estimated) edge count.

    Snaps up to the smallest tier in ``max_edge_tiers`` that holds
    ``n_edges``, so nearby workloads share one jit cache entry, and caps at
    the dense-dispatch default — an autotuned buffer is never larger than
    the hand-tuned one, and past the cap both drop exactly the same
    trailing edges.
    """
    return tier_for(n_edges, max_edge_tiers(height, width, base=base))


def tier_for(n_edges: int, tiers: tuple[int, ...]) -> int:
    """The smallest tier that holds ``n_edges``, else the cap: the host
    twin of the device's tier choice (``tier_index``)."""
    for t in tiers:
        if int(n_edges) <= t:
            return t
    return tiers[-1]


def tier_index(counts: jax.Array, tiers: tuple[int, ...]) -> jax.Array:
    """Index into ``tiers`` of the tier that holds a batch's densest
    frame (capped at the last): the one rule both tiered paths use."""
    worst = counts.max().astype(jnp.int32)
    return jnp.minimum(
        sum((worst > t).astype(jnp.int32) for t in tiers),
        len(tiers) - 1,
    )


def resolved_auto_config(cfg: HoughConfig, n_edges: int, height: int,
                         width: int) -> HoughConfig:
    """Shared tail of ``max_edges="auto"`` resolution: the dense path
    neutralizes the knob (it is inert there, and a stable value keeps jit
    cache keys shared), the compacted path gets the bucketed buffer."""
    if not cfg.compact:
        return dataclasses.replace(cfg, max_edges=None)
    return dataclasses.replace(
        cfg, max_edges=auto_max_edges(n_edges, height, width)
    )


def resolve_max_edges(edges, cfg: HoughConfig) -> HoughConfig:
    """Resolve ``max_edges="auto"`` against a *concrete* edge map.

    The compacted vote buffer is a static shape, so "auto" must become an
    int before tracing; here the edge map is already computed, so the exact
    per-frame count (max over a batch) feeds ``auto_max_edges``.  The
    pipeline resolves earlier — from the raw image, via the downsampled
    gradient estimate in ``canny.estimate_edge_count`` — because under its
    jit the edge map is a tracer.
    """
    if cfg.max_edges != "auto":
        return cfg
    H, W = edges.shape[-2:]
    if not cfg.compact:  # knob inert on the dense path; no count needed
        return resolved_auto_config(cfg, 0, H, W)
    if isinstance(edges, jax.core.Tracer):
        raise ValueError(
            "HoughConfig(max_edges='auto') needs a concrete edge map to "
            "size the compaction buffer; resolve via "
            "LineDetector/resolve_max_edges before jit."
        )
    counts = np.asarray(edges >= cfg.edge_threshold).sum(axis=(-2, -1))
    n = int(counts.max()) if getattr(counts, "ndim", 0) else int(counts)
    return resolved_auto_config(cfg, n, H, W)


def hough_transform(edges: jax.Array, cfg: HoughConfig = HoughConfig(),
                    theta_bins: jax.Array | None = None, *,
                    scatter: bool = True) -> jax.Array:
    """Vote accumulator (..., n_rho, n_theta) from an edge map (..., H, W).

    Thin wrapper resolving ``max_edges="auto"`` (a data-dependent static
    shape) before entering the jitted body below.  ``theta_bins`` carries
    the prediction gate when ``cfg.theta_band`` is set (see
    :class:`HoughConfig`); ``scatter=False`` then keeps the accumulator in
    band space, (..., n_rho, theta_band) — the plan path feeds that
    straight into ``get_lines(theta_bins=...)`` so the whole peak stage
    scales with the band.
    """
    if cfg.max_edges == "auto":
        cfg = resolve_max_edges(edges, cfg)
    return _hough_transform(edges, cfg, theta_bins, scatter=scatter)


def hough_transform_tiered(edges: jax.Array, cfg: HoughConfig,
                           tiers: tuple[int, ...] | None = None,
                           theta_bins: jax.Array | None = None, *,
                           scatter: bool = True) -> jax.Array:
    """Device-side ``max_edges`` autotune: trace-safe tiered dispatch.

    The compaction buffer is a static shape, so a *traced* edge map cannot
    pick an arbitrary size — but it can pick from a small static set.  The
    exact per-frame edge count (a cheap device reduction; max over a batch)
    selects the smallest tier in ``max_edge_tiers`` that holds every edge,
    and ``lax.switch`` runs the one branch compiled for that tier.  No
    host round-trip anywhere: this is how the plan layer (``core/plan.py``)
    keeps ``max_edges="auto"`` streams free of per-chunk syncs.

    Bit-exact with the dense path whenever the chosen tier drops no edges
    (the count is exact, so only the cap tier can drop any — the same
    trailing edges the hand-tuned dense default drops).  The jit cache
    stays finite: one compiled program per (shape, cfg), holding
    ``len(tiers)`` vote variants.
    """
    return hough_transform_counted(edges, cfg, tiers, theta_bins,
                                   scatter=scatter)[0]


def hough_transform_counted(edges: jax.Array, cfg: HoughConfig,
                            tiers: tuple[int, ...] | None = None,
                            theta_bins: jax.Array | None = None, *,
                            scatter: bool = True
                            ) -> tuple[jax.Array, jax.Array | None]:
    """``hough_transform_tiered`` that also returns the per-frame edge
    counts its tier choice read, ``(votes, counts)`` (counts None on the
    dense path, which chooses no tier)."""
    if not cfg.compact:
        return _hough_transform(
            edges, dataclasses.replace(cfg, max_edges=None), theta_bins,
            scatter=scatter,
        ), None
    H, W = edges.shape[-2:]
    if tiers is None:
        tiers = max_edge_tiers(H, W)
    with jax.named_scope("compact"):
        counts = (edges >= cfg.edge_threshold).sum(axis=(-2, -1))
        idx = tier_index(counts, tiers)
    cfgs = [dataclasses.replace(cfg, max_edges=int(t)) for t in tiers]
    if theta_bins is None:
        branches = [
            functools.partial(_hough_transform, cfg=c) for c in cfgs
        ]
        return jax.lax.switch(idx, branches, edges), counts
    branches = [
        functools.partial(
            lambda e, tb, cfg: _hough_transform(e, cfg, tb,
                                                scatter=scatter),
            cfg=c,
        )
        for c in cfgs
    ]
    return jax.lax.switch(idx, branches, edges, theta_bins), counts


@functools.partial(
    jax.jit, static_argnames=("cfg", "scatter")
)
def _hough_transform(edges: jax.Array, cfg: HoughConfig = HoughConfig(),
                     theta_bins: jax.Array | None = None, *,
                     scatter: bool = True) -> jax.Array:
    """Vote accumulator (..., n_rho, n_theta) from an edge map (..., H, W).

    rho = j*cos(theta) + i*sin(theta)  (paper's convention: x=col, y=row),
    shifted by +rho_max and binned at cfg.rho_res.  The shift and the
    resolution are folded into a homogeneous third coordinate so the whole
    stage is literally one GEMM + histogram.  A batch of edge maps
    (N, H, W) shares one raster coordinate table and lowers as one batched
    vote; ``cfg.compact`` routes through the edge-compaction pre-pass;
    ``cfg.theta_band``/``theta_bins`` restrict the sweep to the prediction
    gate (the accumulator stays full width, zero outside the gate).
    """
    _check_gate(theta_bins, cfg)
    H, W = edges.shape[-2:]
    n_rho = rho_bins(H, W, cfg)
    trig = hough_trig(H, W, cfg)

    with jax.named_scope("compact" if cfg.compact else "vote"):
        jj, ii = jnp.meshgrid(jnp.arange(W), jnp.arange(H))
        xy = jnp.stack(
            [jj.ravel(), ii.ravel(), jnp.ones(H * W, jnp.int32)], axis=1
        ).astype(jnp.float32)
        flat = edges.reshape(edges.shape[:-2] + (H * W,))
        weights = (flat >= cfg.edge_threshold).astype(jnp.float32)

    return ops.hough_vote(
        xy, weights, jnp.asarray(trig), n_rho=n_rho, impl=cfg.impl,
        compact=cfg.compact, max_edges=cfg.max_edges,
        theta_bins=theta_bins, scatter_back=scatter,
    )


def _check_corridors(corridors, cfg: HoughConfig) -> None:
    if (corridors is None) != (cfg.corridors is None):
        raise ValueError(
            "HoughConfig.corridors and the corridors argument come as a "
            f"pair (got corridors={cfg.corridors!r}, argument="
            f"{'set' if corridors is not None else None!r})."
        )
    if corridors is not None and corridors.shape != (cfg.corridors, 4):
        raise ValueError(
            f"corridors must have the plan's static shape "
            f"({cfg.corridors}, 4); got {corridors.shape}."
        )


def _check_gate(theta_bins, cfg: HoughConfig) -> None:
    if (theta_bins is None) != (cfg.theta_band is None):
        raise ValueError(
            "HoughConfig.theta_band and the theta_bins argument come as a "
            f"pair (got theta_band={cfg.theta_band!r}, "
            f"theta_bins={'set' if theta_bins is not None else None!r})."
        )
    if theta_bins is not None and theta_bins.shape != (cfg.theta_band,):
        raise ValueError(
            f"theta_bins must have the plan's static band shape "
            f"({cfg.theta_band},); got {theta_bins.shape}."
        )


def fused_hough(image: jax.Array, canny_cfg, cfg: HoughConfig,
                theta_bins: jax.Array | None = None,
                corridors: jax.Array | None = None, *,
                scatter: bool = True) -> jax.Array:
    """The fused hot path: image -> votes with no edge map in HBM.

    Kernel A (``ops.fused_weights``) runs the whole Canny front end,
    thresholds and corridor-filters in VMEM; the raster compaction and
    kernel B (the standard vote over the compacted list) follow.
    Bit-exact with ``canny`` + ``hough_transform`` at full corridor/band
    coverage whenever the edge count fits the compaction buffer (votes are
    small-integer sums in f32 and both paths produce the identical edge
    set).

    ``cfg.max_edges`` must be a resolved int (or None for the dense
    default); ``"auto"`` exists in tiered form (``fused_hough_tiered``).
    """
    if cfg.max_edges == "auto":
        raise ValueError(
            "fused_hough cannot resolve max_edges='auto'; use "
            "fused_hough_tiered."
        )
    H, W = image.shape[-2:]
    max_edges = cfg.max_edges
    if max_edges is None:
        max_edges = ops.default_max_edges(H * W)
    return _fused_hough_tiered(image, canny_cfg, cfg, (int(max_edges),),
                               theta_bins, corridors, scatter=scatter)[0]


def fused_hough_tiered(image: jax.Array, canny_cfg, cfg: HoughConfig,
                       tiers: tuple[int, ...] | None = None,
                       theta_bins: jax.Array | None = None,
                       corridors: jax.Array | None = None, *,
                       scatter: bool = True) -> jax.Array:
    """Tiered ``max_edges`` dispatch for the fused path (trace-safe).

    Kernel A emits the thresholded, corridor-filtered weights, so the tier
    selector counts the surviving edges *exactly* (max over a batch) and
    ``lax.switch``es over compact+vote branches, just like the staged
    ``hough_transform_tiered``: same count, same tier as staged at full
    coverage, and corridors genuinely shrink the tier on cluttered frames.
    Only a genuine overflow of the cap tier drops edges, exactly like the
    staged cap.
    """
    return fused_hough_counted(image, canny_cfg, cfg, tiers, theta_bins,
                               corridors, scatter=scatter)[0]


def fused_hough_counted(image: jax.Array, canny_cfg, cfg: HoughConfig,
                        tiers: tuple[int, ...] | None = None,
                        theta_bins: jax.Array | None = None,
                        corridors: jax.Array | None = None, *,
                        scatter: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """``fused_hough_tiered`` that also returns the per-frame edge counts
    (after the corridor filter) its tier choice read: ``(votes,
    counts)``."""
    H, W = image.shape[-2:]
    if not cfg.compact:
        tiers = (ops.default_max_edges(H * W),)
    elif tiers is None:
        tiers = max_edge_tiers(H, W)
    return _fused_hough_tiered(image, canny_cfg, cfg, tuple(tiers),
                               theta_bins, corridors, scatter=scatter)


@functools.partial(
    jax.jit, static_argnames=("canny_cfg", "cfg", "tiers", "scatter")
)
def _fused_hough_tiered(image: jax.Array, canny_cfg, cfg: HoughConfig,
                        tiers: tuple[int, ...],
                        theta_bins: jax.Array | None = None,
                        corridors: jax.Array | None = None, *,
                        scatter: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """Kernel A, exact-count tier choice, raster compaction, kernel B:
    ``(votes, per-frame edge counts)``.

    The exact post-corridor edge count (the same reduction as
    ``hough_transform_tiered``, on weights instead of the edge map) picks
    the branch; each branch compacts via the raster index scatter and
    votes.  Bit-exact with the staged path at full corridor/band coverage
    because the count — hence the tier — matches the staged dispatch and
    compaction preserves raster order.
    """
    _check_gate(theta_bins, cfg)
    _check_corridors(corridors, cfg)
    H, W = image.shape[-2:]
    n_rho = rho_bins(H, W, cfg)
    trig = jnp.asarray(hough_trig(H, W, cfg))
    with jax.named_scope("canny"):
        w = ops.fused_weights(
            image, corridors, cfg=canny_cfg,
            edge_threshold=cfg.edge_threshold, impl=cfg.impl,
        )
    with jax.named_scope("compact"):
        counts = (w > 0).sum(axis=-1)
        idx = tier_index(counts, tiers)

    def make(t):
        # theta_bins captured by closure (lax.switch branches may close
        # over tracers) so every branch keeps one operand signature.
        def branch(w):
            with jax.named_scope("compact"):
                cxy, cw = ops.compact_raster(w, width=W, max_edges=int(t))
            return ops.hough_vote(
                cxy, cw, trig, n_rho=n_rho, impl=cfg.impl, compact=False,
                theta_bins=theta_bins, scatter_back=scatter,
            )

        return branch

    return jax.lax.switch(idx, [make(t) for t in tiers], w), counts


def hough_paper_loop(edges: jax.Array, cfg: HoughConfig = HoughConfig()
                     ) -> jax.Array:
    """Paper Algorithm 2, faithfully serial: for each edge point, for each
    theta, ``accumulators[(rho + c_rho)*n_theta + theta]++``.

    Implemented as a ``lax.fori_loop`` over pixels with a vectorized inner
    theta sweep — the closest a data-parallel host gets to the scalar-core
    loop while staying jittable.  Used as the measured baseline for the
    Table 7 speedup analogue.
    """
    H, W = edges.shape
    n_rho = rho_bins(H, W, cfg)
    diag = math.hypot(H, W)
    theta = jnp.arange(cfg.n_theta, dtype=jnp.float32) * (
        math.pi / cfg.n_theta
    )
    cos_t, sin_t = jnp.cos(theta), jnp.sin(theta)
    flat = edges.ravel().astype(jnp.float32)

    def body(p, acc):
        i = p // W
        j = p % W
        rho = j * cos_t + i * sin_t + diag
        idx = jnp.floor(rho / cfg.rho_res).astype(jnp.int32)
        w = jnp.where(flat[p] >= cfg.edge_threshold, 1.0, 0.0)
        return acc.at[idx, jnp.arange(cfg.n_theta)].add(w)

    acc0 = jnp.zeros((n_rho, cfg.n_theta), jnp.float32)
    return jax.lax.fori_loop(0, H * W, body, acc0)
