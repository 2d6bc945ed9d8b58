"""Scheduler: frames completed per dispatch over the window (service counters)."""

from chip_bench.layers import frames_per_dispatch as read  # noqa: F401
