"""95th percentile of due-to-acknowledged latency of the events due in
the window; events never acknowledged sit at the top."""
from chipbench.harness import percentile


def read(ctx):
    lat = ctx.latencies_ms()
    return percentile(lat, 95) if len(lat) else None
