"""Backend dispatch policy for the kernel pipeline.

One place decides which of the three executable lanes a kernel call
takes (:func:`kernel_mode`):

  * ``"pallas"``    — compiled ``pallas_call`` on backends with a Pallas
    lowering (TPU / GPU);
  * ``"xla"``       — the compiled lane for backends where ``pallas_call``
    cannot compile (XLA-CPU raises "Only interpret mode is supported"):
    the same tile-blocked math lowered through ``jax.jit`` to native XLA
    codegen (``kernels/xla.py``), where tile sizes become ``lax.map``
    cache-blocking chunks;
  * ``"interpret"`` — ``pallas_call(interpret=True)``, the CPU default:
    validates kernel semantics exactly as written, at interpreter speed.

``REPRO_INTERPRET=auto|on|off`` selects: ``auto`` interprets on CPU and
compiles pallas on TPU/GPU; ``on`` forces interpret everywhere; ``off``
forces the compiled lane (pallas where it compiles, xla on CPU).

Pallas kernels still take ``interpret: bool | None = None`` and resolve
``None`` through :func:`resolve_interpret`; the xla-vs-pallas choice is
made above them, in ``ops.py``, via :func:`kernel_mode`.
"""
from __future__ import annotations

import jax

from .. import env

_PALLAS_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def kernel_mode() -> str:
    """The executable lane for this process: interpret | xla | pallas."""
    v = env.get("REPRO_INTERPRET")
    if v == "on":
        return "interpret"
    pallas_compiles = jax.default_backend() in _PALLAS_BACKENDS
    if v == "off":
        return "pallas" if pallas_compiles else "xla"
    return "pallas" if pallas_compiles else "interpret"


def backend_key() -> str:
    """Tuning-table backend key: ``xla-cpu`` | ``tpu`` | ``gpu``."""
    b = jax.default_backend()
    if b == "tpu":
        return "tpu"
    if b in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "xla-cpu"


def fused_plan_enabled() -> bool:
    """Whether the planner should take the fused pdist→rankeval launch.

    Fusion is a compiled-lane optimization: it is on for the ``pallas``
    and ``xla`` modes and off under interpret, where the staged pipeline
    is the validated reference (the fused kernel itself is still
    test-exercised in interpret mode explicitly).
    """
    return kernel_mode() != "interpret"


def default_interpret() -> bool:
    """Auto policy: True iff this process's lane is pallas-interpret."""
    return kernel_mode() == "interpret"


def compact_enabled() -> bool:
    """Whether the resident executor should gather certified candidate
    rows into a dense bucket before the filter kernels (DESIGN.md §13)
    instead of streaming the full padded slot array."""
    return env.get("REPRO_COMPACT") == "on"


def rows_dtype() -> str | None:
    """Requested reduced-precision filter-plane dtype for snapshot rows:
    ``"bf16"`` | ``"f16"``, or None when the plane is disabled (the
    default — f32 everywhere, bitwise-identical to prior releases)."""
    v = env.get("REPRO_ROWS_DTYPE")
    return None if v in ("off", "f32") else v


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → backend auto-selection; a bool is respected as-is."""
    return default_interpret() if interpret is None else bool(interpret)


__all__ = ["kernel_mode", "backend_key", "fused_plan_enabled",
           "default_interpret", "resolve_interpret", "compact_enabled",
           "rows_dtype"]
