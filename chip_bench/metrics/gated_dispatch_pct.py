"""Plan dispatch: share of dispatches under a union theta gate (service counters)."""

from chip_bench.layers import dispatch_pct

read = dispatch_pct("gated_dispatches")
