"""Each kernel's operations and bytes from its shapes, and its share of
the roofline.

The peaks come from ``bench/peaks.json``, keyed by JAX's
``device_kind``; a device that is not there is an error, not a default.
The kernels multiply in f32 at ``Precision.HIGHEST``, which the MXU runs
as six bf16 passes, so their compute roof is the bf16 peak over
``f32_highest_passes``.  The bytes are the least the algorithm must move
through HBM: each operand read once and the output written once.

Worked example (the 1M x 32 GaussMix snapshot, 1,762,048 slots, a batch
of 64): ``pdist`` reads 64x32 + 1,762,048x32 f32 (225.5 MB) and writes
64 x 1,762,048 f32 (451.1 MB): 0.826 ms at 819 GB/s.  Its 7.2 GFLOP take
0.22 ms at 197/6 TFLOP/s, so the byte roof bounds it.  ``range_filter``
writes one byte per pair instead of four.
"""
from __future__ import annotations

import json
import os

from .spec import BENCH_DIR

F32 = 4


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def pdist_cost(nq: int, npts: int, d: int) -> tuple[float, float]:
    """(flops, bytes) of one squared-L2 ``pdist``: the Gram product plus
    the two norms, operands read once, the (nq, npts) f32 matrix
    written once."""
    flops = 2.0 * nq * npts * d + 2.0 * (nq + npts) * d + 3.0 * nq * npts
    return flops, float(F32 * (nq * d + npts * d + nq * npts))


def range_filter_cost(nq: int, npts: int, d: int) -> tuple[float, float]:
    """(flops, bytes) of one fused ``range_filter``: ``pdist``'s
    arithmetic plus the compare, a radius per query read, and a one-byte
    mask written in place of the f32 distances."""
    flops = 2.0 * nq * npts * d + 2.0 * (nq + npts) * d + 4.0 * nq * npts
    return flops, float(F32 * (nq * d + npts * d + nq) + nq * npts)


COSTS = {"pdist": pdist_cost, "range_filter": range_filter_cost}


def min_seconds(kernel: str, shapes: list, peak: dict) -> tuple[float, str]:
    """(least seconds, bounding roof) for every call's (nq, npts, d)."""
    flop_peak = peak["bf16_flops_per_s"] / peak["f32_highest_passes"]
    t_flops = t_bytes = 0.0
    for nq, npts, d in shapes:
        f, b = COSTS[kernel](nq, npts, d)
        t_flops += f / flop_peak
        t_bytes += b / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "bytes" if t_bytes >= t_flops else "flops"


def share(kernel: str, shapes: list, device_s: float,
          peak: dict) -> float | None:
    """Percent of the roofline the kernel reached, or None with no time
    or no call to judge."""
    if not shapes or device_s <= 0:
        return None
    return 100.0 * min_seconds(kernel, shapes, peak)[0] / device_s
