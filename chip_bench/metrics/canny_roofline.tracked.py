"""Canny kernels (conv2d_gemm, fused_weights): least time for the Gaussian and Sobel work over their device time, %."""

from chip_bench.layers import roofline

read = roofline("canny")
