"""Mean milliseconds per batch of the executor's device stages
(``QueryProfile.stages["execute"]``: kernels, the kNN loop, the copies
to the host) over the window's batches."""


def read(ctx):
    ps = [p.stages["execute"] for p in ctx["profiles"]
          if "execute" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
