"""Host service path: median of finished_at - dispatched_at (device, block and completion) over the frames due in the window and answered in full, ms."""

from chip_bench.program_trace import answer_wait_p50_ms as read  # noqa: F401
