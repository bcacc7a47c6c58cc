"""Device time of `sketch_update` per tick of the window, milliseconds,
from the trace.  The body of `sketch_dev_ms.lat`, for the cells that
report `ingest_eps`."""


def read(ctx):
    if ctx.trace is None or not ctx.ticks_in_window:
        return None
    s, n = ctx.trace.module_seconds(["jit_sketch_update"])
    return s / ctx.ticks_in_window * 1e3 if n else None
