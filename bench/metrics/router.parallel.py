"""How far a routed batch's sub-batches overlapped: the sum of their own
seconds over the dispatch's wall time (``QueryProfile.subbatch_s`` /
``dispatch_s``), averaged over the window's batches.  A sub-batch's own
seconds are its thread's CPU time plus its waits on its device, so time
spent queued for the interpreter behind another replica's host work
does not count.  1.0: the replicas ran one after another; R: all R
worked from start to end together."""


def read(ctx):
    ps = [p.subbatch_s / p.dispatch_s for p in ctx["profiles"]
          if getattr(p, "dispatch_s", None)]
    return sum(ps) / len(ps) if ps else None
