"""Host service path: the service thread's top-level service.* spans, less their service.block and service.stage_wait children, over frames answered in the traced window, ms."""

from chip_bench.program_trace import host_ms_per_frame as read  # noqa: F401
