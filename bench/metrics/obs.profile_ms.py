"""Mean milliseconds per batch the executor spent building the batch's
``QueryProfile`` (``QueryProfile.stages["profile"]``): the
observability's own cost on the served path, over the window's
batches."""


def read(ctx):
    ps = [p.stages["profile"] for p in ctx["profiles"]
          if "profile" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
