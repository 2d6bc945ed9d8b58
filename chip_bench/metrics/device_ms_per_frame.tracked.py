"""Device: busy time, summed over the chips that served, over frames answered in the traced window, ms."""

from chip_bench.layers import device_ms_per_frame as read  # noqa: F401
