"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: the TPU compiler shipped with jaxlib compiles
for a *described* v5e topology, and refuses what the chip's compiler would
refuse (block shapes off the (8, 128) tiling, relayouts Mosaic cannot do,
scoped-VMEM overflows).  Interpret-mode tests cannot see any of that.

Shapes are the detection service's two largest buckets, at batch 1 and at
the service batch (4); the vote runs over the cap compaction tier, both as
the full 180-bin sweep and as the tracker's 40-bin gate.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.canny import CannyConfig, gradient_masks
from repro.core.hough import HoughConfig, hough_trig, rho_bins
from repro.kernels import ops
from repro.kernels.conv2d_gemm import conv2d_gemm
from repro.kernels.fused_detect import fused_weights
from repro.kernels.hough_vote import hough_vote

BUCKETS = [(240, 320), (480, 640)]
BATCHES = [1, 4]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but can never be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("hw", BUCKETS)
def test_conv2d_gemm_compiles(one_chip, hw, batch):
    gauss, sobel = (jnp.asarray(m) for m in gradient_masks(CannyConfig()))

    def step(img):
        s = conv2d_gemm(img, gauss)[:, 0]
        return conv2d_gemm(s, sobel)

    _compile(step, ((batch,) + hw, jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("band", [None, 40])
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("hw", BUCKETS)
def test_hough_vote_compiles(one_chip, hw, batch, band):
    H, W = hw
    cfg = HoughConfig()
    trig = hough_trig(H, W, cfg)
    if band is not None:
        trig = trig[:, 60 : 60 + band]
    n_edges = ops.default_max_edges(H * W)

    def step(xy, w):
        return hough_vote(xy, w, jnp.asarray(trig), n_rho=rho_bins(H, W, cfg))

    _compile(step, ((batch, n_edges, 3), jnp.float32),
             ((batch, n_edges), jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("hw", BUCKETS)
def test_fused_weights_compiles(one_chip, hw, batch):
    def step(img, cor):
        return fused_weights(img, cor, cfg=CannyConfig(),
                             edge_threshold=250.0)

    _compile(step, ((batch,) + hw, jnp.float32), ((4, 4), jnp.float32),
             sharding=one_chip)


def test_fused_weights_refuses_other_tiers():
    img = jnp.asarray(np.zeros((16, 16), np.float32))
    with pytest.raises(ValueError, match="f32 gradient tier"):
        fused_weights(img, cfg=CannyConfig(grad_dtype="f16"),
                      edge_threshold=250.0, interpret=True)
