"""Mean certified candidate rows per query (the rows the exact
refinement scanned; ``QueryProfile.candidates_per_query``), weighted by
batch size, over the window's batches."""


def read(ctx):
    ps = ctx["profiles"]
    n = sum(p.batch for p in ps)
    return sum(p.candidates_per_query * p.batch for p in ps) / n if n else None
