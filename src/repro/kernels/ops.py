"""Jitted public wrappers around the kernel pipeline.

Handle padding to block multiples, lane dispatch and result un-padding.
These are the entry points the rest of the framework calls; nothing else
touches ``pallas_call`` or the XLA lowerings.

Lane dispatch (``repro.kernels.dispatch.kernel_mode``): each call
resolves one of three lanes — compiled ``pallas_call`` on TPU/GPU,
compiled jitted-XLA (``kernels/xla.py``) on CPU under
``REPRO_INTERPRET=off``, and ``pallas_call(interpret=True)`` otherwise
(the CPU default).  The wrappers resolve the policy per call and pass
explicit statics down, so flipping the env var between calls takes
effect (the kernels' jit caches key on lane-distinct functions and the
resolved static tile values).

Tile selection: explicit ``bq``/``bp``/``bg``/``bb`` arguments are
always respected (callers pinning shard-local tiles, the autotuner's
own micro-runs).  ``None`` means policy: interpret mode keeps the
static heuristics below (small-operand shrink + large point tiles to
amortize per-grid-cell interpreter cost); the compiled lanes first
consult the autotuner's tuning table (``kernels/autotune.py``,
``REPRO_AUTOTUNE``) and fall back to the static compiled heuristics on
a miss.  Blocks still shrink to fit small operands — a batch of 3
queries pads to an 8-row tile, not a 128-row one — preserving the
8×128 f32 tile alignment the TPU lane wants (the xla lane only needs
the 8-row sublane granularity; its tiles are ``lax.map`` cache-blocking
chunks).

Shard-local sizing: under ``shard_map`` (the cluster-sharded executor)
each device traces these wrappers with *shard-local* shapes, so the
automatic policy — tuning-table buckets included — sizes blocks to the
per-device slice.  Callers that pin blocks explicitly should derive
them from local operand sizes via :func:`local_blocks` instead of
global corpus constants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune
from .dispatch import fused_plan_enabled, kernel_mode
from .flash_attention import flash_attention_pallas
from .fused import pdist_rankeval_pallas
from .pdist import pdist_pallas
from .range_filter import range_filter_pallas
from .rankeval import rankeval_pallas
from .xla import (pdist_rankeval_xla, pdist_xla, range_filter_xla,
                  rankeval_xla)

from ..obs import registry as _obs

_LANE = 128     # TPU lane width: last-dim tiles stay multiples of this
_SUBLANE = 8    # f32 sublane width: leading-dim tiles align to this


def _count_launch(name: str, mode: str, probe) -> None:
    """Per-kernel dispatch counter (``kernels.<name>.launches`` plus a
    per-lane breakdown).  These wrappers run both eagerly and inside
    jit/shard_map traces; a traced call is bookkeeping at *trace* time,
    not a launch per execution, so tracer operands are skipped — the
    eager call sites (the planner's staged path, the paged backend's
    per-round refinement) are the ones that count."""
    if not _obs.enabled() or isinstance(probe, jax.core.Tracer):
        return
    _obs.count(f"kernels.{name}.launches")
    _obs.count(f"kernels.{name}.{mode}")


def _interpret() -> bool:
    return kernel_mode() == "interpret"


def _tile(n: int, block: int, mult: int = _SUBLANE) -> int:
    """Largest useful block: ``block`` capped at n rounded up to ``mult``."""
    return min(block, -(-max(n, 1) // mult) * mult)


def _lane_mult(interp: bool) -> int:
    """Lane-dim tile granularity: interpret mode can shrink below the
    128-lane TPU tile; the compiled path keeps full alignment."""
    return _SUBLANE if interp else _LANE


def _mode_lane(mode: str) -> int:
    """Lane-dim granularity per lane: only the pallas-compiled lane
    needs the 128-lane alignment; interpret and xla chunk at sublane."""
    return _LANE if mode == "pallas" else _SUBLANE


def _point_block(npts: int, bp: int, interp: bool) -> int:
    """Point-dim tile. Interpret mode executes the kernel body once per
    grid cell in Python, so its cost scales with the cell count, not the
    tile size — grow the tile to cover many points per cell. The compiled
    path keeps the VMEM-sized default."""
    if interp:
        bp = max(bp, 4096)
    return _tile(npts, bp, _lane_mult(interp))


def pad_to(x: jax.Array, mult: int, axis: int = 0,
           fill: float = 0.0) -> jax.Array:
    """Pad ``x`` along ``axis`` with ``fill`` to the next multiple of
    ``mult`` (identity when already aligned)."""
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_rows(x: jax.Array, mult: int, fill: float = 0.0) -> jax.Array:
    return pad_to(x, mult, axis=0, fill=fill)


def _group_tile(g: int, bg: int | None, mode: str, kernel: str) -> int:
    """Group-dim tile of the rank kernels.  In the fused kernel's
    (bb, bg) distance block the groups are the lane dim, so on the
    pallas lane ``bg`` is 128-aligned there; everywhere else it is a
    sublane dim."""
    if kernel == "pdist_rankeval" and mode == "pallas":
        return _tile(g, 128 if bg is None else bg, _LANE)
    default = 64 if mode == "interpret" else 8
    return _tile(g, default if bg is None else bg)


def _qp_tiles(nq: int, npts: int, d: int, metric: str, mode: str,
              bq: int | None, bp: int | None, qb: int | None,
              kernel: str, consult: bool = True) -> tuple[int, int, int]:
    """Resolve the (bq, bp, qb) triple for a query×points kernel under
    the current lane: explicit values win, then the tuning table
    (compiled lanes; ``consult=False`` skips it — the autotuner's own
    static-baseline resolution), then static heuristics.

    ``qb`` is the query *sub*-block of the xla lane's query-blocked
    nest (see ``kernels/xla.py``); 0 means no sub-blocking.  It is
    meaningful only on the xla lane — the pallas grid is point-major,
    which gives the same point-tile reuse structurally — and must
    divide ``bq`` (misaligned overrides degrade to 0, never to a bad
    reshape)."""
    interp = mode == "interpret"
    if consult and not interp and (bq is None or bp is None or qb is None):
        t = autotune.tiles_for(kernel, metric, {"q": nq, "p": npts, "d": d})
        if t:
            bq = t["bq"] if bq is None else bq
            bp = t["bp"] if bp is None else bp
            qb = t.get("qb") if qb is None else qb
    if kernel == "pdist" and metric in ("l1", "linf") and mode != "xla":
        # the pallas kernels cap bq at 32 for the broadcast metrics —
        # cap before padding so unaligned query counts pad to the capped
        # tile, not past it
        bq = min(128 if bq is None else bq, 32)
    bq = _tile(nq, 128 if bq is None else bq)
    if interp:
        bp = _point_block(npts, 128 if bp is None else bp, interp)
    else:
        bp = _tile(npts, 128 if bp is None else bp, _mode_lane(mode))
    if mode != "xla" or qb is None or qb >= min(bq, nq):
        qb = 0
    else:
        qb = _tile(min(bq, nq), qb)
        if qb <= 0 or min(bq, nq) % qb:
            qb = 0
    return bq, bp, qb


def static_tiles(kernel: str, metric: str | None,
                 dims: dict[str, int]) -> dict[str, int]:
    """Static-heuristic tiles for ``kernel`` at ``dims`` under the
    current lane — the autotuner's baseline candidate (never consults
    the tuning table, so tuning can't recurse into a lookup).  ``qb``
    is reported as ``bq`` ("no sub-blocking") so the dict validates as
    a table entry."""
    mode = kernel_mode()
    interp = mode == "interpret"
    if kernel in ("pdist", "range_filter"):
        bq, bp, qb = _qp_tiles(dims["q"], dims["p"], dims["d"],
                               metric or "sql2", mode, None, None, None,
                               kernel, consult=False)
        return {"bq": bq, "bp": bp, "qb": qb or bq}
    if kernel in ("rankeval", "pdist_rankeval"):
        g, b = dims["g"], dims["b"]
        bg = _group_tile(g, None, mode, kernel)
        bb = _point_block(b, 128, interp) if interp \
            else _tile(b, 128, _mode_lane(mode))
        return {"bg": bg, "bb": bb}
    raise ValueError(f"unknown kernel {kernel!r}")


def local_blocks(nq: int, npts: int, bq: int | None = None,
                 bp: int | None = None, metric: str = "sql2",
                 d: int = 8) -> tuple[int, int]:
    """Resolve the (bq, bp) tile pair for (possibly shard-local) operand
    sizes under the current dispatch policy: query tiles align to the
    sublane width, point tiles grow to amortize interpret-mode grid cells
    (compiled lanes instead consult the autotune table) and cap at the
    local point count (lane-aligned per backend).

    This is exactly what ``pdist``/``range_filter`` resolve internally
    from the shapes they receive — callers inside ``shard_map`` get
    shard-local sizing for free.  The helper exists for code that needs
    the policy *outside* a kernel call: benchmarks reporting the tile a
    measurement ran with, and tile-alignment property tests.  ``d`` only
    affects the compiled lanes' tuning-table shape bucket.  (The xla
    lane's query sub-block ``qb`` is an internal chunking of ``bq`` and
    is not part of this pair.)"""
    tbq, tbp, _ = _qp_tiles(nq, npts, d, metric, kernel_mode(), bq, bp,
                            None, "pdist")
    return tbq, tbp


def pdist(q, p, metric: str = "sql2", bq: int | None = None,
          bp: int | None = None, qb: int | None = None):
    """Pairwise distances with automatic padding. metric: sql2 | l1 | linf.
    sql2 returns squared distances (use ``jnp.sqrt`` or square radii).
    ``qb`` is the xla lane's query sub-block (``None`` → policy; ignored
    on the pallas/interpret lanes, whose point-major grid already reuses
    point tiles)."""
    q = jnp.asarray(q)
    p = jnp.asarray(p)
    nq, npts = q.shape[0], p.shape[0]
    mode = kernel_mode()
    _count_launch("pdist", mode, q)
    bq, bp, qb = _qp_tiles(nq, npts, q.shape[1], metric, mode, bq, bp,
                           qb, "pdist")
    qp = _pad_rows(q, bq)
    pp = _pad_rows(p, bp)
    if mode == "xla":
        out = pdist_xla(qp, pp, metric=metric, bq=bq, bp=bp, qb=qb)
    else:
        out = pdist_pallas(qp, pp, metric=metric, bq=bq, bp=bp,
                           interpret=mode == "interpret")
    return out[:nq, :npts]


def rankeval(x, coef, lo, hi, n, n_rings: int = 20,
             bg: int | None = None, bb: int | None = None):
    """Batched rank-model eval (G groups × B values) + ring ids.

    ``bg``/``bb`` override the group/value tile sizes (``None`` → policy
    default, which adapts to the — possibly shard-local — operand and,
    on the compiled lanes, consults the tuning table)."""
    x = jnp.asarray(x, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    g, b = x.shape
    mode = kernel_mode()
    _count_launch("rankeval", mode, x)
    interp = mode == "interpret"
    if not interp and (bg is None or bb is None):
        t = autotune.tiles_for("rankeval", None,
                               {"g": g, "b": b, "c": coef.shape[1]})
        if t:
            bg = t["bg"] if bg is None else bg
            bb = t["bb"] if bb is None else bb
    bg = _group_tile(g, bg, mode, "rankeval")
    # an explicit bb is respected (not grown) but keeps the backend's
    # lane granularity so an override can never break tile alignment
    if interp:
        bb = _point_block(b, 128, interp) if bb is None \
            else _tile(b, bb, _lane_mult(interp))
    else:
        bb = _tile(b, 128 if bb is None else bb, _mode_lane(mode))
    gp, bp_ = (-g) % bg, (-b) % bb
    xq = jnp.pad(x, ((0, gp), (0, bp_)))
    coefq = jnp.pad(coef, ((0, gp), (0, 0)))
    loq = jnp.pad(jnp.asarray(lo, jnp.float32), (0, gp))
    hiq = jnp.pad(jnp.asarray(hi, jnp.float32), (0, gp), constant_values=1.0)
    nq_ = jnp.pad(jnp.asarray(n, jnp.float32), (0, gp))
    if mode == "xla":
        rank, rid = rankeval_xla(xq, coefq, loq, hiq, nq_,
                                 n_rings=n_rings, bg=bg, bb=bb)
    else:
        rank, rid = rankeval_pallas(xq, coefq, loq, hiq, nq_,
                                    n_rings=n_rings, bg=bg, bb=bb,
                                    interpret=interp)
    return rank[:g, :b], rid[:g, :b]


def range_filter(q, p, r, bq: int | None = None, bp: int | None = None,
                 qb: int | None = None):
    """Fused L2-ball membership mask (nq, np) uint8 for batched range
    queries, one radius per query.  ``qb`` as in :func:`pdist`."""
    q = jnp.asarray(q)
    p = jnp.asarray(p)
    r = jnp.asarray(r, jnp.float32)
    nq, npts = q.shape[0], p.shape[0]
    mode = kernel_mode()
    _count_launch("range_filter", mode, q)
    bq, bp, qb = _qp_tiles(nq, npts, q.shape[1], "sql2", mode, bq, bp,
                           qb, "range_filter")
    qp = _pad_rows(q, bq)
    pp = _pad_rows(p, bp, fill=np.inf)     # padding rows never match
    rp = _pad_rows(r, bq, fill=-1.0)
    if mode == "xla":
        mask = range_filter_xla(qp, pp, rp, bq=bq, bp=bp, qb=qb)
    else:
        mask = range_filter_pallas(qp, pp, rp, bq=bq, bp=bp,
                                   interpret=mode == "interpret")
    return mask[:nq, :npts]


def pdist_rankeval(q, piv, coef, lo, hi, n, rg, n_rings: int = 20,
                   bg: int | None = None, bb: int | None = None):
    """Fused plan stage: query→pivot L2 distances + rank eval at the
    widened-radius boundaries dq∓rg, one launch, no staged (G, 2B)
    distance buffer.

    ``q`` (B, d); ``piv`` (G, d); ``coef`` (G, C); ``lo``/``hi``/``n``
    (G,); ``rg`` (B,).  Returns ``(dq (B, G) f32, rank_lo (G, B) i32,
    rank_hi (G, B) i32)`` — bit-identical (within a lane) to the staged
    ``sqrt(max(pdist, 0))`` + ``rankeval(concat(dq-rg, dq+rg))``
    pipeline; the planner selects between them via
    ``dispatch.fused_plan_enabled``.
    """
    q = jnp.asarray(q, jnp.float32)
    piv = jnp.asarray(piv, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    B, d = q.shape
    G, C = coef.shape
    mode = kernel_mode()
    _count_launch("pdist_rankeval", mode, q)
    interp = mode == "interpret"
    if not interp and (bg is None or bb is None):
        t = autotune.tiles_for("pdist_rankeval", None,
                               {"g": G, "b": B, "d": d, "c": C})
        if t:
            bg = t["bg"] if bg is None else bg
            bb = t["bb"] if bb is None else bb
    bg = _group_tile(G, bg, mode, "pdist_rankeval")
    bb = _tile(B, 128 if bb is None else bb, _mode_lane(mode))
    gp = (-G) % bg
    qp = _pad_rows(q, bb)
    rgp = _pad_rows(jnp.asarray(rg, jnp.float32), bb)
    pivp = _pad_rows(piv, bg)
    coefp = jnp.pad(coef, ((0, gp), (0, 0)))
    lop = jnp.pad(jnp.asarray(lo, jnp.float32), (0, gp))
    hip = jnp.pad(jnp.asarray(hi, jnp.float32), (0, gp),
                  constant_values=1.0)
    np_ = jnp.pad(jnp.asarray(n, jnp.float32), (0, gp))
    if mode == "xla":
        dq, rlo, rhi = pdist_rankeval_xla(qp, pivp, coefp, lop, hip, np_,
                                          rgp, n_rings=n_rings, bg=bg,
                                          bb=bb)
    else:
        dq, rlo, rhi = pdist_rankeval_pallas(qp, pivp, coefp, lop, hip,
                                             np_, rgp, n_rings=n_rings,
                                             bg=bg, bb=bb,
                                             interpret=interp)
    return dq[:B, :G], rlo[:G, :B], rhi[:G, :B]


def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 128):
    """Padded flash attention: (B,Hq,Sq,D) × (B,Hk,Sk,D) → (B,Hq,Sq,D)."""
    b, hq, sq, d = q.shape
    _, hk, sk, _ = k.shape
    _count_launch("flash_attention", kernel_mode(), q)
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    # flash attention has no jitted-XLA lane: it compiles only where
    # pallas_call does (TPU/GPU); everywhere else it stays in interpret
    # mode even under REPRO_INTERPRET=off
    out = flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                 interpret=kernel_mode() != "pallas",
                                 kv_len=sk if pk else None)
    return out[:, :, :sq]


__all__ = ["pdist", "rankeval", "range_filter", "pdist_rankeval",
           "flash_attention", "pad_to", "local_blocks", "static_tiles",
           "fused_plan_enabled"]
