"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.conv2d_gemm import conv2d_gemm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hough_vote import compact_edges, hough_vote
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.tiled_matmul import tiled_matmul


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 70, 50), (128, 128, 128),
                                   (33, 129, 65)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tiled_matmul_float(rng, m, k, n, dtype):
    x = rng.normal(size=(m, k)).astype(np.float32)
    y = rng.normal(size=(k, n)).astype(np.float32)
    x = jnp.asarray(x, dtype)
    y = jnp.asarray(y, dtype)
    got = tiled_matmul(x, y, interpret=True, bm=32, bn=32, bk=32)
    want = ref.tiled_matmul(x, y)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (64, 48, 32)])
def test_tiled_matmul_int8(rng, m, k, n):
    x = rng.integers(-127, 127, (m, k), dtype=np.int8)
    y = rng.integers(-127, 127, (k, n), dtype=np.int8)
    got = tiled_matmul(jnp.asarray(x), jnp.asarray(y), interpret=True,
                       bm=16, bn=16, bk=16)
    want = ref.tiled_matmul(jnp.asarray(x), jnp.asarray(y))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("hw", [(16, 24), (37, 52), (64, 64)])
@pytest.mark.parametrize("masks", [(1, 3, 3), (3, 5, 5), (3, 7, 7)])
def test_conv2d_gemm_float(rng, hw, masks):
    H, W = hw
    img = rng.normal(size=(H, W)).astype(np.float32)
    m = rng.normal(size=masks).astype(np.float32)
    got = conv2d_gemm(jnp.asarray(img), jnp.asarray(m), interpret=True, bh=8)
    want = ref.conv2d_gemm(jnp.asarray(img), jnp.asarray(m))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_gemm_int(rng):
    img = rng.integers(0, 255, (40, 56)).astype(np.int32)
    m = rng.integers(-16, 16, (3, 5, 5)).astype(np.int32)
    got = conv2d_gemm(jnp.asarray(img), jnp.asarray(m), interpret=True, bh=8)
    want = ref.conv2d_gemm(jnp.asarray(img), jnp.asarray(m))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("hw,bh,bw", [((21, 19), 8, 8), ((37, 52), 8, 16)])
def test_conv2d_gemm_halo_non_multiple(rng, hw, bh, bw):
    """Halo-tiled grid on shapes that do not divide the block sizes."""
    img = rng.normal(size=hw).astype(np.float32)
    m = rng.normal(size=(3, 7, 7)).astype(np.float32)
    got = conv2d_gemm(jnp.asarray(img), jnp.asarray(m), interpret=True,
                      bh=bh, bw=bw)
    want = ref.conv2d_gemm(jnp.asarray(img), jnp.asarray(m))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_gemm_batched_one_launch(rng):
    """(N, H, W) lowers with a leading batch grid axis == per-frame loop."""
    imgs = rng.normal(size=(3, 21, 37)).astype(np.float32)
    m = rng.normal(size=(3, 5, 5)).astype(np.float32)
    got = conv2d_gemm(jnp.asarray(imgs), jnp.asarray(m), interpret=True,
                      bh=8, bw=16)
    assert got.shape == (3, 3, 21, 37)
    for i in range(3):
        want = conv2d_gemm(jnp.asarray(imgs[i]), jnp.asarray(m),
                           interpret=True, bh=8, bw=16)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))
    wantb = ref.conv2d_gemm(jnp.asarray(imgs), jnp.asarray(m))
    np.testing.assert_allclose(np.asarray(got), np.asarray(wantb),
                               rtol=1e-4, atol=1e-4)


def _vote_inputs(rng, n_pix, n_theta, n_rho, edge_frac=0.15, batch=None,
                 per_frame=False):
    """Vote operands on which every backend computes the same rho.

    Integer coordinates in ``[0, n_rho / 2)`` and trig rows on a 2**-13
    grid make every product and sum exact in f32, fused or not, so votes
    match bit for bit.  rho spans about ``[-0.6, 1.4] x n_rho``: bins below
    0 and at or past ``n_rho`` cast no vote.  Theta 0 (``off - x``) and
    theta 1 (``x + y + off``) land exactly on bin edges, and pixels 0-3
    are edges that sit on bins -1, 0, n_rho - 1 and n_rho.
    """
    off = np.floor(0.4 * n_rho)
    xy = np.ones((n_pix, 3), np.float32)
    xy[:, :2] = rng.integers(0, n_rho // 2, (n_pix, 2))
    # theta 0: off - x = -1, 0; theta 1: x + y + off = n_rho - 1, n_rho
    xy[:4, :2] = [[off + 1, 0], [off, 0],
                  [(n_rho - off - 1) // 2, (n_rho - off) // 2],
                  [(n_rho - off) // 2, (n_rho - off + 1) // 2]]
    trig = np.round(rng.uniform(-1, 1, (3, n_theta)) * 8192) / 8192
    trig[:2, :2] = [[-1.0, 1.0], [0.0, 1.0]]
    trig[2] = off
    trig = trig.astype(np.float32)
    shape = (batch, n_pix) if batch else (n_pix,)
    w = (rng.uniform(size=shape) > 1 - edge_frac).astype(np.float32)
    w[..., :4] = 1.0
    if per_frame:  # each frame its own pixels, shifted a bin or two
        xy = np.stack([xy + [i, i % 2, 0] for i in range(batch)])
    return jnp.asarray(xy), jnp.asarray(w), jnp.asarray(trig)


@pytest.mark.parametrize("n_pix,n_theta,n_rho", [
    (64, 45, 60), (200, 180, 150), (200, 40, 60), (200, 180, 60),
    (200, 40, 300), (200, 45, 300), (200, 180, 300), (300, 40, 801),
    (300, 45, 801), (300, 180, 801),
])
def test_hough_vote(rng, n_pix, n_theta, n_rho):
    """One frame: the MXU vote == the scatter-add oracle, bit for bit, over
    several 128-bin high parts of rho and several pixel blocks."""
    xy, w, trig = _vote_inputs(rng, n_pix, n_theta, n_rho, edge_frac=0.6)
    got = hough_vote(xy, w, trig, n_rho=n_rho, interpret=True, bp=128)
    want = ref.hough_vote(xy, w, trig, n_rho=n_rho)
    assert got.shape == (n_rho, n_theta)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_theta,n_rho", [(90, 150), (40, 801), (180, 300)])
@pytest.mark.parametrize("per_frame", [False, True])
def test_hough_vote_batched(rng, per_frame, n_theta, n_rho):
    """Shared raster coords or per-frame pixel sets + (N, P) weights lower
    as one batched kernel."""
    xy, w, trig = _vote_inputs(rng, 200, n_theta, n_rho, edge_frac=0.3,
                               batch=3, per_frame=per_frame)
    got = hough_vote(xy, w, trig, n_rho=n_rho, interpret=True, bp=128,
                     bt=16)
    assert got.shape == (3, n_rho, n_theta)
    want = ref.hough_vote(xy, w, trig, n_rho=n_rho)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compact_edges_matches_ref(rng):
    """Prefix-sum-scatter compaction == stable-sort oracle, single + batch."""
    from repro.kernels.hough_vote import compact_edges
    xy, w, _ = _vote_inputs(rng, 200, 45, 60)
    for weights in (w, jnp.stack([w, jnp.roll(w, 7)])):
        cxy1, cw1 = compact_edges(xy, weights, max_edges=64)
        cxy2, cw2 = ref.compact_edges(xy, weights, max_edges=64)
        np.testing.assert_array_equal(np.asarray(cxy1), np.asarray(cxy2))
        np.testing.assert_array_equal(np.asarray(cw1), np.asarray(cw2))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_hough_vote_compact_parity(rng, impl):
    """Compacted voting == dense voting == ref oracle, for both impls, and
    the compacted kernel's pixel iteration is bounded by max_edges."""
    from repro.kernels import ops
    from repro.kernels.hough_vote import compact_edges
    max_edges = 64
    xy, _, trig = _vote_inputs(rng, 400, 45, 80)
    wn = np.zeros(400, np.float32)
    wn[rng.choice(400, 50, replace=False)] = 1.0  # 50 edges < max_edges
    w = jnp.asarray(wn)
    dense = ref.hough_vote(xy, w, trig, n_rho=80)
    got = ops.hough_vote(xy, w, trig, n_rho=80, impl=impl, compact=True,
                         max_edges=max_edges)
    # vote counts are small integers in f32: compaction must be *exact*
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.hough_vote_compact(xy, w, trig, n_rho=80,
                                          max_edges=max_edges)),
    )
    # the compacted pixel set — what the vote grid iterates — is static
    # max_edges, not n_pix
    cxy, cw = compact_edges(xy, w, max_edges=max_edges)
    assert cxy.shape == (max_edges, 3) and cw.shape == (max_edges,)
    assert int((w > 0).sum()) <= max_edges  # no drops in this sweep


def test_compact_edges_overflow_drops(rng):
    """Edges past max_edges are dropped, never scattered out of bounds."""
    from repro.kernels.hough_vote import compact_edges
    xy, _, _ = _vote_inputs(rng, 100, 45, 60)
    w = jnp.ones((100,), jnp.float32)  # every pixel is an edge
    cxy, cw = compact_edges(xy, w, max_edges=16)
    assert cxy.shape == (16, 3)
    np.testing.assert_array_equal(np.asarray(cw), np.ones(16, np.float32))
    np.testing.assert_array_equal(np.asarray(cxy), np.asarray(xy)[:16])


# Deterministic twins of the hypothesis properties in test_properties.py
# (that module is skipped wholesale when hypothesis isn't installed, so the
# invariants the compaction fast path rests on are pinned here too).


@pytest.mark.parametrize("seed,density,max_edges",
                         [(0, 1, 16), (1, 5, 64), (2, 9, 64), (3, 3, 96)])
def test_compact_edges_stable_prefix(seed, density, max_edges):
    """Compaction output is exactly the first max_edges edge rows in
    original index order (no permutation, no fabrication), zero-padded."""
    rng2 = np.random.default_rng(seed)
    n_pix = 128
    w = (rng2.uniform(size=n_pix) < density / 10.0).astype(np.float32)
    xy = np.stack([np.arange(n_pix), np.arange(n_pix) * 2,
                   np.ones(n_pix)], axis=1).astype(np.float32)
    idx = np.flatnonzero(w > 0)[:max_edges]
    want_xy = np.zeros((max_edges, 3), np.float32)
    want_w = np.zeros(max_edges, np.float32)
    want_xy[: len(idx)] = xy[idx]
    want_w[: len(idx)] = w[idx]
    for impl in (compact_edges, ref.compact_edges):
        cxy, cw = impl(jnp.asarray(xy), jnp.asarray(w), max_edges=max_edges)
        np.testing.assert_array_equal(np.asarray(cxy), want_xy)
        np.testing.assert_array_equal(np.asarray(cw), want_w)


@pytest.mark.parametrize("seed,density", [(0, 1), (1, 3), (2, 6)])
def test_compacted_vote_bit_exact_when_buffer_fits(seed, density):
    """n_edges <= max_edges => compacted accumulator == dense, bit-exact."""
    from repro.kernels import ops
    rng2 = np.random.default_rng(seed)
    n_pix, n_theta, n_rho = 300, 45, 80
    xy = jnp.asarray(
        np.stack([rng2.uniform(0, 30, n_pix), rng2.uniform(0, 30, n_pix),
                  np.ones(n_pix)], axis=1).astype(np.float32))
    w = jnp.asarray(
        (rng2.uniform(size=n_pix) < density / 10.0).astype(np.float32))
    theta = np.arange(n_theta) * (np.pi / n_theta)
    trig = jnp.asarray(np.stack([
        np.cos(theta), np.sin(theta), np.full_like(theta, 43.0),
    ]).astype(np.float32))
    max_edges = max(8, int(np.asarray(w > 0).sum()))
    dense = ops.hough_vote(xy, w, trig, n_rho=n_rho, impl="xla")
    compact = ops.hough_vote(xy, w, trig, n_rho=n_rho, impl="xla",
                             compact=True, max_edges=max_edges)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(compact))


@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 24),
                                           (False, None)])
def test_flash_attention(rng, gqa, causal, window):
    B, Hq, L, D = 2, 4, 72, 16
    q = rng.normal(size=(B, Hq, L, D)).astype(np.float32)
    k = rng.normal(size=(B, Hq // gqa, L, D)).astype(np.float32)
    v = rng.normal(size=(B, Hq // gqa, L, D)).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, window=window, interpret=True,
                          bq=16, bk=16)
    want = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_decode_offset(rng):
    """Decode: 1 query at the end of a long kv timeline."""
    B, H, Lkv, D = 2, 4, 96, 16
    q = rng.normal(size=(B, H, 1, D)).astype(np.float32)
    k = rng.normal(size=(B, H, Lkv, D)).astype(np.float32)
    v = rng.normal(size=(B, H, Lkv, D)).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, q_offset=Lkv - 1, interpret=True,
                          bq=8, bk=32)
    want = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=True, q_offset=Lkv - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_attention_blockwise_matches_dense_and_grads(rng):
    B, Hq, L, D = 2, 4, 50, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, L, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, 2, L, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, 2, L, D)), jnp.float32)

    out_b = ref.attention_blockwise(q, k, v, causal=True, window=17, block=16)
    out_d = ref.attention(q, k, v, causal=True, window=17)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_d),
                               rtol=1e-4, atol=1e-4)

    def lb(q, k, v):
        return jnp.sum(jnp.sin(ref.attention_blockwise(
            q, k, v, causal=True, window=17, block=16) * 3))

    def ld(q, k, v):
        return jnp.sum(jnp.sin(ref.attention(
            q, k, v, causal=True, window=17) * 3))

    gb = jax.grad(lb, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("chunk", [16, 32])
def test_ssd_scan(rng, G, chunk):
    B, L, H, P, N = 2, 80, 4, 16, 8
    x = (rng.normal(size=(B, L, H, P)) * 0.1).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (B, L, H)).astype(np.float32)
    A = -rng.uniform(0.5, 1.5, (H,)).astype(np.float32)
    Bm = rng.normal(size=(B, L, G, N)).astype(np.float32)
    C = rng.normal(size=(B, L, G, N)).astype(np.float32)
    ya, sa = ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                      jnp.asarray(Bm), jnp.asarray(C), chunk=chunk,
                      interpret=True)
    yb, sb = ref.ssd_scan(x, dt, A, Bm, C)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunked_ref_matches_sequential(rng):
    B, L, H, P, N, G = 2, 100, 4, 16, 8, 2
    x = (rng.normal(size=(B, L, H, P)) * 0.1).astype(np.float32)
    dt = rng.uniform(0.01, 0.1, (B, L, H)).astype(np.float32)
    A = -rng.uniform(0.5, 1.5, (H,)).astype(np.float32)
    Bm = rng.normal(size=(B, L, G, N)).astype(np.float32)
    C = rng.normal(size=(B, L, G, N)).astype(np.float32)
    yc, hc = ref.ssd_scan_chunked(x, dt, A, Bm, C, chunk=32)
    ys, hs = ref.ssd_scan(x, dt, A, Bm, C)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hc), np.asarray(hs),
                               rtol=2e-3, atol=2e-3)
