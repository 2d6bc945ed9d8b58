"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's tests sweep shapes/dtypes
and ``assert_allclose`` against these functions.  They are also the "xla"
execution path used on hosts without a TPU (this container), where XLA's own
fusions are the fastest option and the HLO they produce is what the dry-run
roofline reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiles import acc_dtype as _acc_dtype


def tiled_matmul(x: jax.Array, y: jax.Array, *, out_dtype=None) -> jax.Array:
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    acc = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else x.dtype
    return jnp.dot(x, y, preferred_element_type=acc).astype(out_dtype)


def conv2d_gemm(image: jax.Array, masks: jax.Array, *, out_dtype=None
                ) -> jax.Array:
    """Same-padded 2D correlation; (..., H, W) -> (..., n_masks, H, W)."""
    H, W = image.shape[-2:]
    n_masks, kh, kw = masks.shape
    integer = jnp.issubdtype(image.dtype, jnp.integer)
    acc = _acc_dtype(image.dtype)
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else image.dtype
    pad = [(0, 0)] * (image.ndim - 2) + [
        (kh // 2, kh // 2), (kw // 2, kw // 2)
    ]
    padded = jnp.pad(image, pad)
    # im2col in HBM: (..., H, W, kh*kw) patch tensor, then one contraction.
    patches = jnp.stack(
        [
            padded[..., dy : dy + H, dx : dx + W]
            for dy in range(kh)
            for dx in range(kw)
        ],
        axis=-1,
    ).astype(acc)
    flat = masks.reshape(n_masks, kh * kw).astype(acc)
    # HIGHEST: on a TPU the default would round f32 operands to bf16.
    out = jnp.einsum("...hwk,mk->...mhw", patches, flat,
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(out_dtype)


def conv2d_stencil(image: jax.Array, masks: jax.Array, *, out_dtype=None
                   ) -> jax.Array:
    """Scalar-core formulation: per-tap shift-multiply-accumulate, no GEMM.

    This is the paper's *baseline* execution (the stencil as written, before
    the matrix rewrite of Workload 3) — kept as a measurable path so the
    benchmarks can report the GEMM-offload speedup the way Table 7 does.
    """
    H, W = image.shape[-2:]
    n_masks, kh, kw = masks.shape
    integer = jnp.issubdtype(image.dtype, jnp.integer)
    acc = _acc_dtype(image.dtype)
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else image.dtype
    pad = [(0, 0)] * (image.ndim - 2) + [
        (kh // 2, kh // 2), (kw // 2, kw // 2)
    ]
    padded = jnp.pad(image, pad).astype(acc)
    outs = []
    for m in range(n_masks):
        o = jnp.zeros(image.shape, acc)
        for dy in range(kh):
            for dx in range(kw):
                o = o + masks[m, dy, dx].astype(acc) * padded[
                    ..., dy : dy + H, dx : dx + W
                ]
        outs.append(o)
    return jnp.stack(outs, axis=-3).astype(out_dtype)


def grad_hits(image: jax.Array, *, stride: int, thresh: float
              ) -> jax.Array:
    """Downsampled finite-difference gradient hit count (per frame).

    The reduction behind the ``max_edges`` autotune estimator
    (``core.canny.estimate_edge_count_device``): subsample by ``stride``,
    take |dx|/|dy| finite differences as a stand-in for Sobel-of-Gaussian,
    and count coarse pixels whose stronger difference clears ``thresh``.
    Returns an int32 count per leading-axis frame ((..., H, W) -> (...)).
    Element-wise + reduction — VPU work, no Pallas variant needed; it lives
    here so the estimator shares the kernel package's dispatch/oracle
    structure.
    """
    img = jnp.asarray(image, jnp.float32)
    sub = img[..., ::stride, ::stride]
    gx = jnp.abs(sub[..., :, 1:] - sub[..., :, :-1])[..., :-1, :]
    gy = jnp.abs(sub[..., 1:, :] - sub[..., :-1, :])[..., :, :-1]
    hit = jnp.maximum(gx, gy) >= thresh
    return hit.sum(axis=(-2, -1), dtype=jnp.int32)


def hough_vote(xy: jax.Array, weights: jax.Array, trig: jax.Array,
               *, n_rho: int) -> jax.Array:
    """Scatter-add vote oracle (the paper's Algorithm 2, vectorized).

    ``weights`` may be batched (N, n_pix) — with ``xy`` either shared
    (n_pix, C) or per-frame (N, n_pix, C) — returning (N, n_rho, n_theta).
    """
    if weights.ndim == 2:
        if xy.ndim == 3:
            return jax.vmap(
                lambda x, w: hough_vote(x, w, trig, n_rho=n_rho)
            )(xy, weights)
        return jax.vmap(
            lambda w: hough_vote(xy, w, trig, n_rho=n_rho)
        )(weights)
    # HIGHEST: a TPU's default pass rounds the operands to bf16, which
    # moves pixel coordinates past 256 by whole bins; on the CPU this is
    # the plain f32 dot.
    rho = jnp.dot(xy.astype(jnp.float32), trig.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST)  # (P, n_theta)
    idx = jnp.floor(rho).astype(jnp.int32)
    n_theta = trig.shape[1]
    votes = jnp.zeros((n_rho, n_theta), jnp.float32)
    inside = (idx >= 0) & (idx < n_rho)
    idx = jnp.clip(idx, 0, n_rho - 1)
    w = jnp.where(inside, weights.astype(jnp.float32)[:, None], 0.0)
    t = jnp.broadcast_to(jnp.arange(n_theta)[None, :], idx.shape)
    return votes.at[idx.ravel(), t.ravel()].add(w.ravel())


def compact_edges(xy: jax.Array, weights: jax.Array, *, max_edges: int):
    """Edge-compaction oracle: stable partition of edge pixels to the front.

    Same contract as ``hough_vote.compact_edges`` (which uses a prefix-sum
    scatter) but formulated as a stable argsort so the two implementations
    are independent: rows past the edge count — and edges beyond
    ``max_edges`` — are zeroed/dropped.
    """
    if weights.ndim == 2:
        if xy.ndim == 3:
            return jax.vmap(
                lambda x, w: compact_edges(x, w, max_edges=max_edges)
            )(xy, weights)
        return jax.vmap(
            lambda w: compact_edges(xy, w, max_edges=max_edges)
        )(weights)
    mask = weights > 0
    order = jnp.argsort(~mask, stable=True)[:max_edges]
    keep = mask[order]
    cxy = jnp.where(keep[:, None], xy[order], jnp.zeros_like(xy[order]))
    cw = jnp.where(keep, weights[order], jnp.zeros_like(weights[order]))
    return cxy, cw


def hough_vote_compact(xy: jax.Array, weights: jax.Array, trig: jax.Array,
                       *, n_rho: int, max_edges: int) -> jax.Array:
    """Compacted-vote oracle: compact edges, then vote over max_edges rows."""
    cxy, cw = compact_edges(xy, weights, max_edges=max_edges)
    return hough_vote(cxy, cw, trig, n_rho=n_rho)


def hough_vote_gated(xy: jax.Array, weights: jax.Array, trig: jax.Array,
                     theta_bins: jax.Array, *, n_rho: int) -> jax.Array:
    """Theta-gated vote oracle: the full sweep with every column outside
    the gate zeroed.

    The semantics of record for ``ops.hough_vote(theta_bins=...)`` — which
    gathers the gated trig columns, votes over the narrow band, and
    scatters back — formulated independently (full vote + mask) so the two
    implementations share no code path.  Duplicate gate bins are
    idempotent in both forms.
    """
    full = hough_vote(xy, weights, trig, n_rho=n_rho)
    mask = (
        jnp.zeros((trig.shape[1],), bool).at[theta_bins].set(True)
    )
    return jnp.where(mask, full, jnp.zeros_like(full))


def snap_corridors(corridors: jax.Array) -> jax.Array:
    """Corridor rows with ``cos``/``sin`` snapped to multiples of 2**-13.

    With integer pixel coordinates below 2**11 every product of the rho test
    and their sum are then exact f32, so the test gives the same answer on
    every backend whether or not it fuses the multiply-add (XLA on the CPU
    does).  A corridor edge moves by at most ``(W + H) * 2**-14`` px (0.07
    px at 480x640).
    """
    cor = jnp.asarray(corridors, jnp.float32)
    normal = jnp.floor(cor[:, :2] * 8192.0 + 0.5) * (1.0 / 8192.0)
    return jnp.concatenate([normal, cor[:, 2:]], axis=1)


def corridor_keep(xy: jax.Array, corridors: jax.Array) -> jax.Array:
    """Which pixels fall inside at least one rho corridor.

    ``corridors`` is (C, 4) f32 rows ``[cos(theta_c), sin(theta_c),
    rho_lo, rho_hi]`` — a window around one predicted lane in *signed,
    unshifted* rho (``x*cos + y*sin``, the same convention ``get_lines``
    decodes peaks into, so tracker state plugs in directly).  A pixel
    survives if its rho along any corridor's normal lands in that
    corridor's window; padding rows just repeat a real corridor (the OR is
    idempotent).  ``hough.full_corridors`` builds windows that pass
    everything.  The normals are snapped first (``snap_corridors``).

    ``xy`` is (..., P, C>=2) with columns (x, y, ...); returns (..., P) bool.
    """
    xyf = xy[..., :2].astype(jnp.float32)
    cor = snap_corridors(corridors)
    # Elementwise, like the fused kernel (a TPU runs a K=2 dot in bf16).
    rho = xyf[..., 0:1] * cor[:, 0] + xyf[..., 1:2] * cor[:, 1]  # (..., P, C)
    return ((rho >= cor[:, 2]) & (rho <= cor[:, 3])).any(axis=-1)


def fused_weights(image: jax.Array, *, cfg, edge_threshold: float,
                  corridors: jax.Array | None = None) -> jax.Array:
    """Flat edge weights of the fused hot path, pre-compaction.

    Runs the full Canny front end (forced onto the pure-jnp "xla" impl so
    the oracle never recurses into Pallas), weights pixels by the edge
    threshold exactly as the staged ``hough`` stage does, and zeroes the
    weights of pixels outside every corridor.  Returns ``(..., H*W)`` f32 —
    the intermediate the fused module's exact tier selector counts before
    compaction (``core.hough.fused_hough_tiered``); the oracle of kernel A
    (``kernels.fused_detect.fused_weights``).
    """
    import dataclasses

    from repro.core.canny import canny as _canny  # function-level: cycle

    edges = _canny(image, dataclasses.replace(cfg, impl="xla"))
    H, W = edges.shape[-2:]
    flat = edges.reshape(edges.shape[:-2] + (H * W,))
    w = (flat >= edge_threshold).astype(jnp.float32)
    if corridors is not None:
        jj, ii = jnp.meshgrid(jnp.arange(W), jnp.arange(H))
        xy = jnp.stack([jj.ravel(), ii.ravel()], axis=1).astype(jnp.float32)
        w = w * corridor_keep(xy, corridors).astype(jnp.float32)
    return w


def compact_raster(weights: jax.Array, *, width: int, max_edges: int):
    """Raster-layout edge compaction: scatter flat *indices*, not rows.

    The generic ``compact_edges`` moves ``(x, y, 1)`` coordinate rows
    through the scatter because its ``xy`` operand is arbitrary.  The
    fused path owns the raster layout, so the pixel coordinate is a pure
    function of the flat index — compaction only needs to scatter one
    int32 per surviving pixel and reconstruct ``(idx % W, idx // W, 1)``
    from the ``(max_edges,)`` result afterwards, a quarter of the scatter
    payload.  The fused path runs it after kernel A on every backend.

    Same contract as ``compact_edges``: raster order, rows past the edge
    count zeroed, edges beyond ``max_edges`` dropped — and bit-identical
    output (integer pixel coordinates are exact in f32 either way).
    """
    if weights.ndim == 2:
        return jax.vmap(
            lambda w: compact_raster(w, width=width, max_edges=max_edges)
        )(weights)
    n_pix = weights.shape[-1]
    mask = weights > 0
    pos = jnp.where(mask, jnp.cumsum(mask) - 1, max_edges)
    idx = (
        jnp.zeros((max_edges,), jnp.int32)
        .at[pos]
        .set(jnp.arange(n_pix, dtype=jnp.int32), mode="drop")
    )
    slot = jnp.arange(max_edges) < mask.sum()
    cw = jnp.where(slot, weights[idx], 0.0)
    cxy = jnp.stack(
        [
            (idx % width).astype(jnp.float32),
            (idx // width).astype(jnp.float32),
            jnp.ones((max_edges,), jnp.float32),
        ],
        axis=1,
    )
    return jnp.where(slot[:, None], cxy, 0.0), cw


def attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """Dense softmax attention oracle (GQA via head repeat)."""
    B, Hq, Lq, D = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (D ** 0.5)
    q_pos = q_offset + jnp.arange(Lq)[:, None]
    kv_pos = jnp.arange(Lkv)[None, :]
    mask = jnp.ones((Lq, Lkv), bool)
    if causal:
        mask &= q_pos >= kv_pos
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


import functools as _functools


def _abw_mask(q_pos, kv_pos, Lkv, causal, window):
    mask = kv_pos[None, :] < Lkv
    if causal:
        mask = mask & (q_pos[:, None] >= kv_pos[None, :])
    if window is not None:
        mask = mask & ((q_pos[:, None] - kv_pos[None, :]) < window)
    return mask


def _abw_fwd_impl(q, k, v, causal, window, q_offset, block):
    """Forward online-softmax over kv blocks; returns (out, lse)."""
    B, Hq, Lq, D = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    pad = (-Lkv) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = k.shape[2] // block
    qf = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Lq)

    ks = jnp.moveaxis(k.reshape(B, Hkv, n_blocks, block, D), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, Hkv, n_blocks, block, D), 2, 0)

    def step(carry, inp):
        acc, m, l, j = carry
        kb, vb = inp
        kb = jnp.repeat(kb, rep, axis=1).astype(jnp.float32)
        vb = jnp.repeat(vb, rep, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        kv_pos = j * block + jnp.arange(block)
        mask = _abw_mask(q_pos, kv_pos, Lkv, causal, window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.where(mask[None, None], jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (acc, m_new, l, j + 1), None

    acc0 = jnp.zeros((B, Hq, Lq, D), jnp.float32)
    m0 = jnp.full((B, Hq, Lq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hq, Lq, 1), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, jnp.int32(0)), (ks, vs)
    )
    out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
    # lse = m + log l; empty rows get +inf so exp(s - lse) == 0 in bwd
    lse = jnp.where(
        l == 0.0, jnp.inf, jnp.where(jnp.isinf(m), 0.0, m) + jnp.log(
            jnp.where(l == 0.0, 1.0, l))
    )
    return out, lse


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_blockwise(q, k, v, causal, window, q_offset, block):
    out, _ = _abw_fwd_impl(q, k, v, causal, window, q_offset, block)
    return out


def _abw_fwd(q, k, v, causal, window, q_offset, block):
    out, lse = _abw_fwd_impl(q, k, v, causal, window, q_offset, block)
    return out, (q, k, v, out, lse)


def _abw_bwd(causal, window, q_offset, block, res, do):
    """Flash-style backward: recompute per-block p from (q, k, v, lse);
    O(Lq*D + block^2) live memory — the residuals are the layer I/O only.
    """
    q, k, v, out, lse = res
    B, Hq, Lq, D = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    pad = (-Lkv) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = k.shape[2] // block
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Lq)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1, keepdims=True)

    ks = jnp.moveaxis(k.reshape(B, Hkv, n_blocks, block, D), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, Hkv, n_blocks, block, D), 2, 0)

    def step(dq, inp):
        kb, vb, j = inp
        kbr = jnp.repeat(kb, rep, axis=1).astype(jnp.float32)
        vbr = jnp.repeat(vb, rep, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kbr) * scale
        kv_pos = j * block + jnp.arange(block)
        mask = _abw_mask(q_pos, kv_pos, Lkv, causal, window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jnp.exp(s - lse)                       # (B, Hq, Lq, block)
        p = jnp.where(mask[None, None], p, 0.0)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vbr)
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kbr)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        # fold GQA group: sum query heads sharing a kv head
        dv_j = dv_j.reshape(B, Hkv, rep, block, D).sum(axis=2)
        dk_j = dk_j.reshape(B, Hkv, rep, block, D).sum(axis=2)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Hq, Lq, D), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0, (ks, vs, jnp.arange(n_blocks))
    )
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, Hkv, n_blocks * block, D)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, Hkv, n_blocks * block, D)
    if pad:
        dk = dk[:, :, :Lkv]
        dv = dv[:, :, :Lkv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attention_blockwise.defvjp(_abw_fwd, _abw_bwd)


def attention_blockwise(q, k, v, *, causal=True, window=None, q_offset=0,
                        block=512):
    """Online-softmax attention as a ``lax.scan`` over kv blocks.

    Mathematically identical to ``attention`` but O(Lq * block) peak memory
    instead of O(Lq * Lkv), with a flash-style ``custom_vjp`` backward that
    recomputes block scores from (q, k, v, lse) — the jnp expression of the
    Pallas flash kernel's dataflow, used by the 4k/32k/500k lowering cells
    where a dense (Lq, Lkv) score tensor cannot exist.
    """
    return _attention_blockwise(q, k, v, causal, window, q_offset, block)


def ssd_scan_chunked(x, dt, A, B, C, *, chunk=128):
    """Chunked SSD in jnp — the same segment-sum matmul form as the Pallas
    kernel (``ssd_scan.py``), scanned over chunks.  This is the lowering
    path for train/prefill cells: compact HLO (one chunk body), O(L/Q)
    sequential depth, no (L, N, P) tensor ever materialized.
    """
    x, dt, A, B, C = map(jnp.asarray, (x, dt, A, B, C))
    batch, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Q = min(chunk, L)
    pad = (-L) % Q
    xdt = (x * dt[..., None]).astype(jnp.float32)        # (b, L, H, P)
    ldec = (dt * A[None, None, :]).astype(jnp.float32)   # (b, L, H)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ldec = jnp.pad(ldec, ((0, 0), (0, pad), (0, 0)))
        Bf = jnp.pad(Bf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cf = jnp.pad(Cf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (L + pad) // Q

    def to_chunks(t):
        return jnp.moveaxis(
            t.reshape((batch, nc, Q) + t.shape[2:]), 1, 0
        )

    xs = (to_chunks(xdt), to_chunks(ldec), to_chunks(Bf), to_chunks(Cf))

    def step(h, inp):
        xc, lc, Bc, Cc = inp              # (b,Q,H,P), (b,Q,H), (b,Q,G,N)
        Bh = jnp.repeat(Bc, rep, axis=2)  # (b, Q, H, N)
        Ch = jnp.repeat(Cc, rep, axis=2)
        cum = jnp.cumsum(lc, axis=1)      # (b, Q, H) inclusive
        # intra-chunk: masked decay GEMM
        cb = jnp.einsum("bqhn,bkhn->bhqk", Ch, Bh)
        seg = jnp.exp(cum[:, :, None] - cum[:, None, :])  # (b,Q,Q,H)->perm
        seg = jnp.where(
            jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :],
            seg.transpose(0, 3, 1, 2), 0.0,
        )                                  # (b, H, Q, Q) lower-tri decay
        y = jnp.einsum("bhqk,bkhp->bqhp", cb * seg, xc)
        # inter-chunk: carried state
        y = y + jnp.einsum("bqhn,bhnp->bqhp", Ch, h) * \
            jnp.exp(cum).transpose(0, 1, 2)[..., None]
        # state update
        wB = Bh * jnp.exp(cum[:, -1:, :] - cum)[..., None]
        h = jnp.exp(cum[:, -1])[..., None, None] * h + jnp.einsum(
            "bqhn,bqhp->bhnp", wB, xc
        )
        return h, y

    h0 = jnp.zeros((batch, H, N, P), jnp.float32)
    hL, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(batch, nc * Q, H, P)[:, :L]
    return y.astype(x.dtype), hL


def ssd_scan(x, dt, A, B, C):
    """Sequential selective-scan oracle: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t."""
    x, dt, A, B, C = map(jnp.asarray, (x, dt, A, B, C))
    batch, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)  # (batch, L, H, N)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(h, t):
        a = jnp.exp(dtf[:, t] * A[None, :])  # (batch, H)
        u = jnp.einsum("bh,bhn,bhp->bhnp", dtf[:, t], Bh[:, t], xf[:, t])
        h = a[..., None, None] * h + u
        y = jnp.einsum("bhn,bhnp->bhp", Ch[:, t], h)
        return h, y

    h0 = jnp.zeros((batch, H, N, P), jnp.float32)
    h_final, ys = jax.lax.scan(step, h0, jnp.arange(L))
    y = ys.transpose(1, 0, 2, 3)  # (batch, L, H, P)
    return y.astype(x.dtype), h_final
