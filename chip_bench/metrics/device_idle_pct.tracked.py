"""Device: share of the traced window in which no op ran on the chip, %."""

from chip_bench.layers import device_idle_pct as read  # noqa: F401
