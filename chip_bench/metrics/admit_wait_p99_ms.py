"""Scheduler: 99th percentile of admitted_at - submitted_at over the frames due in the window and answered in full, ms."""

from chip_bench.program_trace import admit_wait_p99_ms as read  # noqa: F401
