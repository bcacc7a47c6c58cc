"""Dictionary references per committed batch edge over the window's
commits (commit stats: refs over instructions less new nodes), percent.
The body of `dict_hit_rate.lat`, for the cells that report
`ingest_eps`."""


def read(ctx):
    refs = sum(c.refs for c in ctx.commits if c.ok)
    edges = sum(c.instructions - c.new_nodes for c in ctx.commits if c.ok)
    return 100.0 * refs / edges if edges else None
