"""Share of the commits' probe rounds that placed a lane, percent: the
program's counters `CommitRecord.rounds_needed` (the rounds that placed
each sweep's last lane) over `CommitRecord.rounds_run` (the rounds the
sweeps' loops executed), summed over the window's commits.  It reads a
counter, not a span.  None where the program keeps no such counter."""


def read(ctx):
    done = [c for c in ctx.commits if c.ok]
    run = sum(getattr(c, "rounds_run", 0) for c in done)
    if not run:
        return None
    return 100.0 * sum(c.rounds_needed for c in done) / run
