#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, by a sweep.

    python3 bench/sweep.py --workload gm32-knn10-open --rates 50,100,150 \\
        --seconds 20 --seed 1

One process builds the cell once, warms it, and offers each rate for one
window (the traffic file's own rate is ignored).  A rate is sustained
when nothing is shed and the backlog does not grow: the last third of
the window's requests wait no longer, at the median, than twice the
first third's.  Each rate prints one JSON line; the cell's traffic file
then fixes its rate at about four fifths of the highest sustained one.
The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from limsbench import cell as C  # noqa: E402
from limsbench import loadgen, traffic  # noqa: E402
from limsbench.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    try:
        ses = C.Session(load_cell(args.workload))
    except C.NoChip as e:
        C.log(f"refused: {e}")
        return 2
    tr = ses.cell.traffic
    for rate in (float(r) for r in args.rates.split(",")):
        due = traffic.arrivals(rate, args.seconds, args.seed)
        reqs = traffic.requests(tr, ses.X, len(due), args.seed, {})
        ses.warm(reqs)
        win = ses.window(reqs, due, args.seconds)
        recs = win["records"]
        lat = loadgen.latencies_ms(recs)
        third = max(1, len(lat) // 3)
        first, last = np.median(lat[:third]), np.median(lat[-third:])
        done = [r for r in recs if r.result is not None]
        span = max(r.done for r in done) - win["t0"] if done else 0.0
        shed = sum(r.shed for r in recs)
        print(json.dumps({
            "rate": rate, "requests": len(recs), "shed": shed,
            "completed_per_s": len(done) / span if span else 0.0,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_third_p50_ms": float(first),
            "last_third_p50_ms": float(last),
            "batch_mean": win["frontend"]["queries"]
            / max(win["frontend"]["batches"], 1),
            "sustained": bool(shed == 0 and last <= 2.0 * first)}),
            flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
