"""Compaction: device time of the detection program's ops under the compact scope (count, tier choice, prefix-sum scatter) over frames answered in the traced window, ms."""

from chip_bench.program_trace import compaction_ms_per_frame as read  # noqa: F401
