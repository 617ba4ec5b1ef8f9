"""What decides ``correct``: the timed path's own answers against the
plain reference.

After the window closes, a sample of the answered requests, drawn from
the seed, is compared with brute force.  A quarter of the sample is the
requests with the most results (the longest answers); the rest is
drawn at random.  Each answer is put in (distance, id) order and must
give the reference's ids and the reference's f64 distances bit for bit:
the service is exact, so every limit is 0.
"""
from __future__ import annotations

import numpy as np

from . import reference
from .traffic import rng

# every compared number and its limit: a run is correct when each value
# is at or below its limit (an exact service allows no gap)
LIMITS = {"wrong_ids": 0, "max_dist_gap": 0.0, "unanswered": 0}


def canonical(ids, d) -> tuple[np.ndarray, np.ndarray]:
    ids = np.asarray(ids, np.int64)
    d = np.asarray(d, np.float64)
    order = np.lexsort((ids, d))
    return ids[order], d[order]


def sample(records: list, size: int, seed: int) -> list:
    """Up to ``size`` answered records: the longest quarter, then a
    seeded draw from the rest."""
    done = [r for r in records if r.result is not None]
    if len(done) <= size:
        return done
    by_len = sorted(range(len(done)),
                    key=lambda i: (-len(done[i].result[0]), i))
    top = by_len[:size // 4]
    rest = np.array(sorted(set(range(len(done))) - set(top)))
    pick = rng(seed, 5).choice(rest, size - len(top), replace=False)
    return [done[i] for i in sorted(top + pick.tolist())]


def compare(got: list, want: list) -> dict:
    """``wrong_ids``: answers whose ids differ from the reference's;
    ``max_dist_gap``: the widest |distance - reference| over the rest."""
    wrong, gap = 0, 0.0
    for (gi, gd), (ri, rd) in zip(got, want):
        gi, gd = canonical(gi, gd)
        ri, rd = canonical(ri, rd)
        if not np.array_equal(gi, ri):
            wrong += 1
            continue
        if len(gd):
            gap = max(gap, float(np.max(np.abs(gd - rd))))
    return {"wrong_ids": wrong, "max_dist_gap": gap}


def judge(X: np.ndarray, records: list, size: int, seed: int,
          unanswered: int, answer_fn=None) -> tuple[bool, dict, int]:
    """(correct, {name: {"value", "limit"}}, compared): correct when
    at least one answer was compared and every value is within its limit.

    ``answer_fn(requests)`` stands in for the program's answers; it is
    how the control puts the lower-precision reference in the program's
    place."""
    picked = sample(records, size, seed)
    want = reference.answers(X, [r.req for r in picked])
    got = [r.result for r in picked] if answer_fn is None else \
        answer_fn([r.req for r in picked])
    nums = compare(got, want)
    nums["unanswered"] = unanswered
    checks = {k: {"value": nums[k], "limit": LIMITS[k]} for k in LIMITS}
    ok = bool(picked) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks, len(picked)
