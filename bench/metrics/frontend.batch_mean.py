"""Queries per dispatched batch in the window, from the frontend's own
``frontend.queries`` and ``frontend.batches`` counters."""


def read(ctx):
    fe = ctx["frontend"]
    return fe["queries"] / fe["batches"] if fe["batches"] else None
