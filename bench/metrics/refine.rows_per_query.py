"""Mean rows per query the exact f64 host refinement scanned
(``QueryProfile.refined_per_query``: a kNN query's certified set cut to
its k-th-distance band, a range query's hits), weighted by batch size,
over the window's batches.  Reads nothing from a program whose profiles
lack the field."""


def read(ctx):
    ps = [p for p in ctx["profiles"]
          if getattr(p, "refined_per_query", None) is not None]
    n = sum(p.batch for p in ps)
    return sum(p.refined_per_query * p.batch for p in ps) / n if n else None
