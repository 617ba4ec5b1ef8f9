"""Hardware profiles for the roofline model.

Published per-chip peaks live in one table, :data:`DEVICE_PEAKS`, keyed
by ``jax.Device.device_kind``; :func:`machine_profile` returns the
``{"name", "peak_flops", "mem_bw"}`` ceiling a roofline divides by.
An accelerator whose kind is not in the table is an
error, never a default.  On CPU the peaks are *calibrated* — a jitted
f32 GEMM for peak FLOP/s and a large streaming elementwise op for
memory bandwidth, best-of several runs, cached per process — because
there is no one datasheet number for "the CI runner's CPU" and an
unmeasured ceiling would make every utilization figure fiction.
"""
from __future__ import annotations

import time

# Source: Google Cloud documentation, "TPU v5e" — per chip: 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
DEVICE_PEAKS = {
    "TPU v5 lite": {"name": "tpu-v5e", "peak_flops": 197e12,
                    "mem_bw": 819e9},
}

_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = _V5E["peak_flops"]    # FLOP/s
HBM_BW = _V5E["mem_bw"]                 # bytes/s
ICI_BW = 50e9                 # bytes/s per link (≈ usable per direction)
DCN_BW = 25e9                 # bytes/s per host, inter-pod (approximate)
HBM_BYTES = 16 * 2**30        # 16 GiB HBM per chip
VMEM_BYTES = 16 * 2**20       # ~16 MiB more-or-less usable VMEM
MXU_DIM = 128

_cpu_profile: dict | None = None


def _best_of(fn, reps: int = 5) -> float:
    import jax
    jax.block_until_ready(fn())          # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _calibrate_cpu() -> dict:
    """Measured f32 GEMM peak + stream bandwidth for this host."""
    global _cpu_profile
    if _cpu_profile is not None:
        return _cpu_profile
    import jax
    import jax.numpy as jnp
    import numpy as np

    m = 1024
    a = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((m, m)).astype(np.float32))
    mm = jax.jit(lambda x: x @ x)
    t_mm = _best_of(lambda: mm(a))
    peak_flops = 2.0 * m ** 3 / t_mm

    n = 1 << 25                           # 128 MiB f32 — far beyond LLC
    v = jnp.ones((n,), jnp.float32)
    stream = jax.jit(lambda x: x * 1.0001 + 1.0)
    t_st = _best_of(lambda: stream(v))
    mem_bw = 2.0 * n * 4 / t_st           # one read + one write stream

    _cpu_profile = {"name": "xla-cpu (calibrated)",
                    "peak_flops": peak_flops, "mem_bw": mem_bw}
    return _cpu_profile


def machine_profile() -> dict:
    """The roofline ceiling for this process's first device: published
    peaks for an accelerator (``KeyError`` for a kind not in
    :data:`DEVICE_PEAKS`), calibrated peaks for the CPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _calibrate_cpu()
    try:
        return dict(DEVICE_PEAKS[dev.device_kind])
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {dev.device_kind!r} "
            f"({dev.platform}); add them to DEVICE_PEAKS") from None
