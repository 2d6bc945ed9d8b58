"""Kernel hough_vote: edge pixels (each capped at its batch's compaction tier) over tier x batch bucket, over the window's dispatches (service counters), %."""

from chip_bench.program_trace import vote_slot_use_pct as read  # noqa: F401
