"""Mean device-to-host syncs per batch (``QueryProfile.host_syncs``),
over the window's executed batches (one per replica a batch is split
across; the first carries the planning and routing syncs).  Read only
from profiles that carry the batch's own transfer record
(``d2h_bytes``): in a program without it, the count through the
frontend is a per-thread total that grows batch after batch."""


def read(ctx):
    ps = [p.host_syncs for p in ctx["profiles"] if hasattr(p, "d2h_bytes")]
    return sum(ps) / len(ps) if ps else None
