"""Mean milliseconds per batch of the exact f64 host refinement
(``QueryProfile.stages["refine"]``) over the window's batches."""


def read(ctx):
    ps = [p.stages["refine"] for p in ctx["profiles"]
          if "refine" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
