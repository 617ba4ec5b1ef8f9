"""Mean milliseconds per batch the router spent assigning the batch's
queries to replicas, the routing's evaluation and copy included
(``QueryProfile.stages["route"]``), over the window's batches."""


def read(ctx):
    ps = [p.stages["route"] for p in ctx["profiles"] if "route" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
