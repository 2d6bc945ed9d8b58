"""Scheduler: median of dispatched_at - admitted_at (the wait for the batch to close) over the frames due in the window and answered in full, ms."""

from chip_bench.program_trace import fill_wait_p50_ms as read  # noqa: F401
