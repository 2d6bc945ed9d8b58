"""Quickstart: detect lane lines in a synthetic road frame (the paper's app).

    PYTHONPATH=src python examples/quickstart.py [--out lines.png]
"""

import argparse
import math

import jax.numpy as jnp
import numpy as np

from repro.core import CannyConfig, LineDetector, PipelineConfig
from repro.data.images import synthetic_road
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write rendered PNG here")
    ap.add_argument("--integer", action="store_true",
                    help="paper §4.4 integer pipeline")
    ap.add_argument("--fused", action="store_true",
                    help="beyond-paper fused 7x7 single-pass masks")
    args = ap.parse_args()

    scene = synthetic_road(240, 320, seed=3)
    det = LineDetector(PipelineConfig(
        canny=CannyConfig(integer=args.integer, fused=args.fused),
        render_output=args.out is not None,
    ))
    res = det.detect(jnp.asarray(
        scene.image, jnp.int32 if args.integer else jnp.float32))

    print("planted lines (rho, theta_deg):")
    for rho, theta in scene.lines_rho_theta:
        print(f"  rho={float(rho):7.1f}  theta={math.degrees(float(theta)):6.1f}")
    print("detected lines:")
    for (rho, theta), ok in zip(np.asarray(res.peaks), np.asarray(res.valid)):
        if ok:
            print(f"  rho={float(rho):7.1f}  theta={math.degrees(float(theta)):6.1f}")

    if args.out:
        from PIL import Image
        Image.fromarray(np.asarray(res.rendered)).save(args.out)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
