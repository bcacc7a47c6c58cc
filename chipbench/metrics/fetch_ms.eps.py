"""Device-to-host pulls on the hot path per tick, milliseconds: the
program's `transform.fetch` (the table's node and edge counts),
`commit.fetch` (the commit's stats) and `loop.fetch` (the table's
ratio, size and density) spans, summed over the window, over its
`tick` spans.  None where the program writes no such span."""

FETCH_SPANS = ("transform.fetch", "commit.fetch", "loop.fetch")


def read(ctx):
    ticks = sum(1 for n, _a, _b in ctx.spans if n == "tick")
    if not ticks or not any(n in FETCH_SPANS for n, _a, _b in ctx.spans):
        return None
    return sum(ctx.span_total(n) for n in FETCH_SPANS) / ticks * 1e3
