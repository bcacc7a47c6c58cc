"""Events acknowledged per second: every event pulled in the window and
acknowledged, over the seconds from the window's opening until the wait
for the last of them ended.  At the close no event is offered any more,
the batch in flight is waited for, and the clock is read after that
wait, so all of that work counts over all of that time."""
import numpy as np


def read(ctx):
    acked = int(np.sum(~np.isnan(ctx.ack[:ctx.pulled])))
    return acked / ctx.end_s if ctx.end_s > 0 else None
