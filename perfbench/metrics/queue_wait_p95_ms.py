"""95th percentile of the wait from a request's due time to the start of
the call that served it (the benchmark's open loop; host clock)."""

import numpy as np


def read(ctx):
    w = ctx.window
    if ctx.mix["loop"] != "open" or not w.served.any():
        return None
    return float(np.percentile((w.start - w.due)[w.served], 95)) * 1e3
