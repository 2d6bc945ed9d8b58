"""End to end: 99th percentile latency of the frames due in the window, from the due time to the terminal answer, ms."""

from chip_bench.latency import percentile_ms

read = percentile_ms(99)
