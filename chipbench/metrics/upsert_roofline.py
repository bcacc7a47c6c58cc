"""The commits' lookup-or-insert passes as a share of the chip's HBM
roofline, percent: the bytes they need, reckoned from each committed
table's shape (`chipbench.roofline.upsert_bytes`), over their device
time, over the peak bandwidth.  Their device time is the top-level
`while` ops inside the commit programs: `ingest_step` has exactly two
loops, the node and the edge probe sweeps."""
from chipbench.readers import COMMIT_PROGRAMS
from chipbench.roofline import upsert_bytes


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.lanes:
        return None
    t = ctx.trace.op_seconds_within("while", COMMIT_PROGRAMS)
    if t <= 0:
        return None
    need = sum(upsert_bytes(lanes, kb) for lanes, kb in ctx.lanes)
    return 100.0 * need / t / ctx.peaks["hbm_bytes_per_s"]
