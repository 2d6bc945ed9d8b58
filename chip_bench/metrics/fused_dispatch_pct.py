"""Plan dispatch: share of dispatches on the fused path (service counters)."""

from chip_bench.layers import dispatch_pct

read = dispatch_pct("fused_dispatches")
