"""Public jit'd wrappers for the kernels package.

Every op dispatches between three implementations:

  * ``pallas``    — compiled Pallas TPU kernel (the deployment path),
  * ``interpret`` — the same kernel body executed in Pallas interpret mode
                    (CPU correctness validation; what the tests use),
  * ``xla``       — the pure-jnp oracle in ``ref.py`` (fast on CPU hosts and
                    the path the dry-run lowers, so roofline FLOP/byte counts
                    come from clean HLO dots rather than interpreter loops).

The default is chosen from the backend at call time and can be forced via
``repro.kernels.ops.set_default_impl(...)`` or ``REPRO_KERNEL_IMPL``.
This mirrors the paper's heterogeneous dispatch: the same call site runs on
the accelerator when one is attached and on the host pipeline otherwise.
On a TPU backend ``interpret`` is refused: it would run the kernel bodies
on the host while the run looks like a chip run.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .conv2d_gemm import conv2d_gemm as _conv_pallas
from .flash_attention import flash_attention as _attn_pallas
from .fused_detect import fused_weights as _fused_weights_pallas
from .hough_vote import compact_edges as _compact_edges
from .hough_vote import hough_vote as _hough_pallas
from .ssd_scan import ssd_scan as _ssd_pallas
from .tiled_matmul import tiled_matmul as _matmul_pallas

_VALID = ("pallas", "interpret", "xla", "stencil")
_default_impl: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    if impl is not None and impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {impl!r}")
    global _default_impl
    _default_impl = impl


def resolve_impl(impl: Optional[str] = None) -> str:
    on_tpu = jax.default_backend() == "tpu"
    chosen = impl or _default_impl or os.environ.get("REPRO_KERNEL_IMPL")
    if chosen is None:
        return "pallas" if on_tpu else "xla"
    if chosen not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {chosen!r}")
    if chosen == "interpret" and on_tpu:
        raise ValueError(
            "impl='interpret' runs Pallas kernel bodies on the host; it is "
            "refused on a TPU backend (use 'pallas', or name 'xla' for the "
            "jnp reference)"
        )
    return chosen


def tiled_matmul(x, y, *, out_dtype=None, impl=None, **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.tiled_matmul(x, y, out_dtype=out_dtype)
    return _matmul_pallas(
        x, y, out_dtype=out_dtype, interpret=(impl == "interpret"), **kw
    )


def conv2d_gemm(image, masks, *, out_dtype=None, impl=None, **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.conv2d_gemm(image, masks, out_dtype=out_dtype)
    if impl == "stencil":   # paper-baseline scalar path (no GEMM rewrite)
        return ref.conv2d_stencil(image, masks, out_dtype=out_dtype)
    return _conv_pallas(
        image, masks, out_dtype=out_dtype, interpret=(impl == "interpret"),
        **kw,
    )


def default_max_edges(n_pix: int) -> int:
    """Hand-tuned edge-compaction buffer default: 1/16 of the pixel count.

    The single source of truth for the dense-dispatch buffer size — the
    autotune cap (``repro.core.hough.auto_max_edges``) and the benchmarks
    reference it so "auto never allocates a larger buffer" stays true if
    this is ever retuned.
    """
    return max(256, n_pix // 16)


def grad_hits(image, *, stride, thresh, impl=None):
    """Downsampled-gradient hit count (the autotune estimator's reduction).

    Element-wise + reduction (VPU work): every impl routes to the jnp form
    in ``ref.py`` — a Pallas variant would buy nothing, but the dispatch
    seam keeps the estimator swappable like every other op here.
    """
    del impl  # single implementation; signature matches the package
    return ref.grad_hits(image, stride=stride, thresh=thresh)


def fused_weights(image, corridors=None, *, cfg, edge_threshold, impl=None):
    """Kernel A: thresholded, corridor-filtered flat edge weights.

    The whole Canny front end, the vote-weight threshold and the corridor
    filter in one dispatch; returns ``(..., H*W)`` f32 in raster order.
    The fused module counts these exactly to pick its compaction tier
    (``core.hough.fused_hough_tiered``), then compacts
    (``compact_raster``) and votes (kernel B).  The oracle is
    ``ref.fused_weights``.
    """
    impl = resolve_impl(impl)
    if impl in ("xla", "stencil"):
        return ref.fused_weights(
            image, cfg=cfg, edge_threshold=edge_threshold,
            corridors=corridors,
        )
    return _fused_weights_pallas(
        image, corridors, cfg=cfg, edge_threshold=edge_threshold,
        interpret=(impl == "interpret"),
    )


def compact_raster(weights, *, width, max_edges):
    """Raster-layout compaction: scatter flat indices, rebuild (x, y, 1).

    ``compact_edges`` with the coordinate rows taken out of the scatter
    payload — valid whenever the caller owns the raster layout (the fused
    hot path).  Bit-identical output to ``compact_edges`` on the same
    weights; see ``ref.compact_raster`` for the layout argument.  A prefix
    sum and a scatter, so XLA runs it on every backend.
    """
    return ref.compact_raster(weights, width=width, max_edges=max_edges)


def hough_vote(xy, weights, trig, *, n_rho, impl=None, compact=False,
               max_edges=None, theta_bins=None, scatter_back=True, **kw):
    """Hough voting with optional edge compaction and theta gating.

    ``compact=True`` runs the prefix-sum edge-compaction pre-pass first so
    the vote stage iterates at most ``max_edges`` pixels (default: 1/16 of
    the pixel count) instead of the full raster — the streaming fast path
    for sparse edge maps.  Both the compacted and dense variants dispatch to
    the same pallas/interpret/xla backends.

    ``theta_bins`` (a traced int32 vector of theta-bin indices, shared
    across any weight batch) is the prediction-gated fast path: the gated
    trig columns are gathered and the backend votes over only that band.
    With ``scatter_back=True`` the band scatters back into a full-width
    accumulator (zeros outside the gate) so every downstream consumer
    keeps full-sweep indexing; ``scatter_back=False`` returns the raw
    (..., n_rho, band) accumulator for consumers that stay in band space
    (``core.lines.get_lines(theta_bins=...)`` — the whole peak stage then
    scales with the band, not n_theta).  The band *length* is a static
    shape — ``core.hough.HoughConfig.theta_band`` pins it at the plan
    layer — while the bin values stay runtime data, so a tracker can
    slide the gate every frame without recompiling.  With ``theta_bins ==
    arange(n_theta)`` the gather and scatter are both identities and the
    result is bit-exact with the ungated call; the oracle is
    ``ref.hough_vote_gated``.  Duplicate bins are allowed (static
    padding): duplicate columns compute identical values and the scatter
    writes them idempotently.
    """
    impl = resolve_impl(impl)
    if compact:
        if isinstance(max_edges, str):
            raise TypeError(
                "max_edges='auto' is a core-layer knob; resolve it to an "
                "int before kernel dispatch (repro.core.hough."
                "resolve_max_edges / auto_max_edges)."
            )
        if max_edges is None:
            max_edges = default_max_edges(weights.shape[-1])
        with jax.named_scope("compact"):
            xy, weights = _compact_edges(xy, weights, max_edges=max_edges)
    with jax.named_scope("vote"):
        n_theta_full = trig.shape[1]
        if theta_bins is not None:
            trig = jnp.asarray(trig)[:, theta_bins]
        if impl == "xla":
            votes = ref.hough_vote(xy, weights, trig, n_rho=n_rho)
        else:
            votes = _hough_pallas(
                xy, weights, trig, n_rho=n_rho,
                interpret=(impl == "interpret"), **kw,
            )
        if theta_bins is not None and scatter_back:
            votes = (
                jnp.zeros(votes.shape[:-1] + (n_theta_full,), votes.dtype)
                .at[..., theta_bins]
                .set(votes)
            )
    return votes


# Above this kv length the xla path switches from dense scores to the
# blockwise-scan form (identical math, O(L*block) memory) so 32k prefill
# cells lower without materializing L^2 score tensors.
_XLA_DENSE_MAX_KV = 2048


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    impl=None, **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        if k.shape[2] > _XLA_DENSE_MAX_KV:
            return ref.attention_blockwise(
                q, k, v, causal=causal, window=window, q_offset=q_offset
            )
        return ref.attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    return _attn_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        interpret=(impl == "interpret"), **kw,
    )


# Above this sequence length the xla path uses the chunked segment-sum SSD
# (one chunk body in HLO) instead of the L-step sequential oracle.
_XLA_SSD_SEQ_MAX = 64


def ssd_scan(x, dt, A, B, C, *, impl=None, **kw):
    impl = resolve_impl(impl)
    if impl == "xla":
        if x.shape[1] > _XLA_SSD_SEQ_MAX:
            return ref.ssd_scan_chunked(x, dt, A, B, C,
                                        chunk=kw.get("chunk", 128))
        return ref.ssd_scan(x, dt, A, B, C)
    return _ssd_pallas(x, dt, A, B, C, interpret=(impl == "interpret"), **kw)
