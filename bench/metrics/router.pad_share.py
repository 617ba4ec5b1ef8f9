"""Percent of the rows dispatched to replicas in the window that were
padding: the copies that fill a sub-batch up to its replica's one shape
(``QueryProfile.pad_rows`` over ``rows + pad_rows``, on each batch's
first sub-batch's profile)."""


def read(ctx):
    ps = [p for p in ctx["profiles"] if getattr(p, "rows", None) is not None]
    rows = sum(p.rows + p.pad_rows for p in ps)
    return 100.0 * sum(p.pad_rows for p in ps) / rows if rows else None
