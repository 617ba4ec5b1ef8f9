"""Mean megabytes (1e6 bytes) copied from the device to the host per
batch (``QueryProfile.d2h_bytes``: every copy the batch made — the
planner's seed distances, the router's routing, the executor's masks),
over the window's executed batches.  A batch split across replicas is
one executed batch per replica, and only the first carries the
planning and routing copies, so the window's sum counts each copy
once."""


def read(ctx):
    ps = [p.d2h_bytes for p in ctx["profiles"] if hasattr(p, "d2h_bytes")]
    return sum(ps) / len(ps) / 1e6 if ps else None
