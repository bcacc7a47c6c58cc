"""Readers that more than one metric file uses.  A quantity whose cells
report different end-to-end metrics is split into one metric per
end-to-end metric (`.eps` moves `ingest_eps`, `.lat` moves
`latency_p95_ms`); each metric's file takes its reader from here."""

# the jitted programs that commit a batch to the store
COMMIT_PROGRAMS = ("jit_ingest_step", "jit_commit_compressed")


def commit_dev_ms(ctx):
    """Device time of one commit program (`ingest_step` or
    `commit_compressed`), milliseconds, from the trace."""
    if ctx.trace is None:
        return None
    s, n = ctx.trace.module_seconds(COMMIT_PROGRAMS)
    return s / n * 1e3 if n else None


def device_idle(ctx):
    """Share of the traced window in which no program ran on the chip,
    percent."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def loop_self_ms(ctx):
    """Self time of the pipeline's `tick` span per tick, milliseconds: the
    loop's own work outside every instrumented stage."""
    s, n = ctx.span_self("tick")
    return s / n * 1e3 if n else None
