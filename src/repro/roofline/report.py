"""Render roofline reports: ``python -m repro.roofline.report [DIR]``
prints the dry-run tables from ``results/dryrun`` JSON records.
"""
from __future__ import annotations

import json
import os
import sys


def load(dirpath: str) -> list[dict]:
    out = []
    for f in sorted(os.listdir(dirpath)):
        if f.endswith(".json"):
            with open(os.path.join(dirpath, f)) as fh:
                out.append(json.load(fh))
    return out


def fmt_ms(s: float) -> str:
    return f"{s*1e3:,.1f}"


def roofline_table(recs: list[dict], mesh: str) -> str:
    rows = ["| arch | shape | params | live GB | fits | t_comp ms | "
            "t_mem ms | t_coll ms | dominant | useful | MFU-bound |",
            "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if r.get("mesh") != mesh:
            continue
        if "skipped" in r:
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — "
                        f"| — | SKIP (full attn @500k) | — | — |")
            continue
        rf = r["roofline"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | "
            f"{r['params_total']/1e9:.1f}B | "
            f"{r['mem']['live_gb']:.1f} | "
            f"{'Y' if r['mem']['fits_16gb'] else 'N'} | "
            f"{fmt_ms(rf['t_compute_s'])} | {fmt_ms(rf['t_memory_s'])} | "
            f"{fmt_ms(rf['t_collective_s'])} | {rf['bottleneck']} | "
            f"{rf['useful_flops_ratio']:.2f} | {rf['mfu_bound']*100:.0f}% |")
    return "\n".join(rows)


def collective_summary(recs: list[dict]) -> str:
    rows = ["| arch | shape | mesh | all-reduce GB | all-gather GB | "
            "a2a GB | permute GB | cross-pod GB |",
            "|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if "skipped" in r:
            continue
        cb = r["roofline"]["coll_detail"]["bytes"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{cb.get('all-reduce', 0)/1e9:.2f} | "
            f"{cb.get('all-gather', 0)/1e9:.2f} | "
            f"{cb.get('all-to-all', 0)/1e9:.2f} | "
            f"{cb.get('collective-permute', 0)/1e9:.2f} | "
            f"{r['roofline']['cross_pod_bytes']/1e9:.2f} |")
    return "\n".join(rows)


def main() -> None:
    d = sys.argv[1] if len(sys.argv) > 1 else "results/dryrun"
    recs = load(d)
    print("## Roofline — single pod (16×16 = 256 chips)\n")
    print(roofline_table(recs, "16x16"))
    print("\n## Roofline — multi-pod (2×16×16 = 512 chips)\n")
    print(roofline_table(recs, "2x16x16"))
    print("\n## Collective breakdown (per device per step)\n")
    print(collective_summary([r for r in recs
                              if r.get("mesh") == "2x16x16"]))


if __name__ == "__main__":
    main()
