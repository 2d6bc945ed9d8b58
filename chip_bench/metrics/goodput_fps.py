"""End to end: frames answered in full inside the window, over its length, frames/s."""

from chip_bench.latency import window_goodput_fps as read  # noqa: F401
