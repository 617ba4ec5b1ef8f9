"""99th percentile of the frontend's own queue wait (from a request's
arrival at the frontend to its batch's dispatch), over every request it
dispatched in the open-loop window."""
import numpy as np


def read(ctx):
    w = ctx["waits_s"]
    if ctx["loop"] != "open" or not w:
        return None
    return float(np.percentile(np.asarray(w) * 1e3, 99))
