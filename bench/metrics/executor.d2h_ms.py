"""Mean milliseconds per batch the host spent copying device values to
the host, after each was ready (``QueryProfile.stages["d2h"]``, summed
over the batch's copies), over the window's batches."""


def read(ctx):
    ps = [p.stages["d2h"] for p in ctx["profiles"] if "d2h" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
