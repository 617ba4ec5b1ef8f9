"""Mean milliseconds per routed batch of the router's threaded dispatch,
from the start of its first sub-batch to the join of its last
(``QueryProfile.dispatch_s``, on each batch's first sub-batch's
profile), over the window's batches."""


def read(ctx):
    ps = [p.dispatch_s for p in ctx["profiles"]
          if getattr(p, "dispatch_s", None) is not None]
    return 1e3 * sum(ps) / len(ps) if ps else None
