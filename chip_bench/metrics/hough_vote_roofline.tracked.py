"""Kernel hough_vote: least time the chip could take for the vote's work over the kernel's device time, %."""

from chip_bench.layers import roofline

read = roofline("hough_vote")
