"""Host CREATEEDGE time (`transform.map` span) per event committed in
the window, in microseconds."""


def read(ctx):
    n = ctx.events_committed()
    return ctx.span_total("transform.map") / n * 1e6 if n else None
