"""How late the load generator sent: 99th percentile of send time minus due time, ms."""

from chip_bench.layers import gen_lag_p99_ms as read  # noqa: F401
