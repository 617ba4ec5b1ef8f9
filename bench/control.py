#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program over many seeds,
and the controls that must fail.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        --kernel-precision default

One process builds the cell once and runs a short window per seed at
the cell's own load, then compares a seeded sample of the answers with
brute force, as a benchmark run does.  Without ``--kernel-precision``
each seed also reads the first control: the reference computed in
float32, one step below the f64 the configuration states, put in the
program's place.  With ``--kernel-precision default`` the Pallas
kernels' f32 products run at the MXU's default precision (one bf16
pass) instead of ``Precision.HIGHEST`` — the step a later change might
take for speed — and the program's own answers are the control.  Each
seed prints one JSON line.  The benchmark's own runs never run this.
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
from limsbench import reference  # noqa: E402
from limsbench.spec import load_cell  # noqa: E402


def lower_kernel_precision() -> None:
    """Run every ``jax.lax.dot_general`` asked for at HIGHEST at the
    default precision instead.  The kernels look the function up when
    they are traced, so this must come before the first compile."""
    import jax
    orig = jax.lax.dot_general
    highest = jax.lax.Precision.HIGHEST

    def dot_general(*a, precision=None, **kw):
        if precision == highest:
            precision = jax.lax.Precision.DEFAULT
        return orig(*a, precision=precision, **kw)
    jax.lax.dot_general = dot_general


def f32_answers(X):
    return lambda reqs: reference.answers(X, reqs, dtype=np.float32)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--kernel-precision", choices=("highest", "default"),
                   default="highest")
    args = p.parse_args(argv)
    if args.kernel_precision == "default":
        lower_kernel_precision()
    try:
        ses = C.Session(load_cell(args.workload))
    except C.NoChip as e:
        C.log(f"refused: {e}")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        reqs, due = ses.traffic(seed, args.seconds)
        ses.warm(reqs)
        win = ses.window(reqs, due, args.seconds)
        ok, checks = C.judge(ses, win, seed)
        line = {"workload": args.workload, "seed": seed,
                "kernel_precision": args.kernel_precision,
                "program": {"correct": ok, "checks": checks}}
        if args.kernel_precision == "highest":
            ok32, c32 = C.judge(ses, win, seed, f32_answers(ses.X))
            line["f32_reference"] = {"correct": ok32, "checks": c32}
        print(json.dumps(line), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
