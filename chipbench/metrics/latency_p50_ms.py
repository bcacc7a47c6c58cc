"""Median due-to-acknowledged latency of the events due in the window."""
from chipbench.harness import percentile


def read(ctx):
    lat = ctx.latencies_ms()
    return percentile(lat, 50) if len(lat) else None
