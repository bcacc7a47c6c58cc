"""Share of the rewritten tables' lanes that held no edge, percent: the
program's per-commit counter `CommitRecord.lanes_idle` (the lanes of a
rewritten table's residual and references with no edge, the counter
`rewrite.lanes_idle` of the committed tables) over those lanes, which
are the idle ones plus one per edge, summed over the window's commits.
None where the program keeps no such counter."""


def read(ctx):
    done = [c for c in ctx.commits if c.ok]
    idle = sum(getattr(c, "lanes_idle", 0) for c in done)
    if not idle:
        return None
    edges = sum(c.instructions - c.new_nodes for c in done)
    return 100.0 * idle / (idle + edges)
