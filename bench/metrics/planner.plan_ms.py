"""Mean milliseconds per batch the planner spent building the plan
(``QueryProfile.stages["plan"]``) over the window's batches."""


def read(ctx):
    ps = [p.stages["plan"] for p in ctx["profiles"] if "plan" in p.stages]
    return 1e3 * sum(ps) / len(ps) if ps else None
