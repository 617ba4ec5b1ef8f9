"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and persists every section's
rows to ``BENCH_<section>.json`` (same top-level shape as
``BENCH_serving.json``: a ``bench`` description plus the payload) so
the perf trajectory is tracked across PRs instead of only printed.
All writes go through ``common.write_json`` (temp file + atomic
rename), so an interrupted run can't truncate a tracked bench file.
``BENCH_QUICK=1`` shrinks scales — quick runs never overwrite the
committed full-run numbers.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    from . import (bench_batch, bench_build, bench_kernels, bench_knn,
                   bench_misc, bench_range, common)
    sections = [
        # slug None: bench_kernels writes its own structured BENCH_kernels.json
        ("kernels", None, bench_kernels.main),
        ("batch engine (serving)", "batch", bench_batch.main),
        # slug None: bench_build writes its own structured BENCH_build.json
        ("build/retrain (host vs device builder)", None, bench_build.main),
        ("range (Fig 6/7)", "range", bench_range.main),
        ("knn (Fig 9/10)", "knn", bench_knn.main),
        ("params/signature/build/updates/ablation (Fig 5/8/11-14)",
         "misc", bench_misc.main),
    ]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print("name,us_per_call,derived")
    failures = 0
    for name, slug, fn in sections:
        t0 = time.time()
        print(f"# --- {name}", file=sys.stderr)
        common.reset_results()
        ok = True
        try:
            fn()
        except Exception:  # noqa: BLE001
            ok = False
            failures += 1
            traceback.print_exc()
        rows = common.snapshot_results()
        # only complete sections persist — a section that died mid-run
        # must not truncate the committed trajectory with partial rows
        if ok and slug and rows and not common.QUICK:
            common.write_json(os.path.join(root, f"BENCH_{slug}.json"),
                              {"bench": name, "rows": rows})
        print(f"# --- {name} done in {time.time()-t0:.0f}s",
              file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark sections failed")


if __name__ == "__main__":
    from repro.env import use_compile_cache
    use_compile_cache()
    main()
