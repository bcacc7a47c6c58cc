"""Host mapping per event, microseconds: the transform's `transform.map`
per committed event plus the filter-time sketch's `sketch.update` (its
own mapping, table build and sketch dispatch) per pulled event."""


def read(ctx):
    n, p = ctx.events_committed(), ctx.pulled_in_window
    if not n or not p:
        return None
    return (ctx.span_total("transform.map") / n
            + ctx.span_total("sketch.update") / p) * 1e6
