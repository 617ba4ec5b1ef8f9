"""95th percentile of how late the load generator handed a request to
the frontend after the instant it was due, in the open-loop window: a
starved generator shows here, not as a fast server."""
import numpy as np

from limsbench import loadgen


def read(ctx):
    late = loadgen.late_ms(ctx["records"])
    if ctx["loop"] != "open" or not len(late):
        return None
    return float(np.percentile(late, 95))
