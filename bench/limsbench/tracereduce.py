"""From a profiler trace to numbers: device busy time, idle share,
per-kernel device time, and idle gaps named by what the host was doing.

A trace is first cut down to plain lists (:func:`extract`), the form a
test fixture holds:

    {"device": {plane: [[op, start_ns, dur_ns, module], ...]},
     "host":   [[span, start_ns, dur_ns], ...]}

``device`` holds every op of each device's "XLA Ops" line; ``host``
holds the benchmark's own ``TraceAnnotation`` spans (names starting
``bench.``), on the same clock.  :func:`reduce` does the rest.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "no layer span (clients, queue, Python between layers)"


def _op_name(text: str) -> str:
    """"%pdist_pallas.1 = f32[..] custom-call(..)" -> "pdist_pallas.1"."""
    return text.split(" = ", 1)[0].lstrip("%")


def _module_name(text: str) -> str:
    """"jit_pdist_pallas(7960022277215020343)" -> "jit_pdist_pallas"."""
    return text.split("(", 1)[0]


def extract(log_dir: str) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``log_dir``:
    each op of a device's "XLA Ops" line with the program ("XLA
    Modules" event) it ran in, and the host's ``bench.`` spans."""
    import jax
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: sorted(((e.start_ns, e.duration_ns, e.name)
                                        for e in line.events))
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            mods = lines.get("XLA Modules", [])
            ops, j = [], 0
            for start, dur, text in lines.get("XLA Ops", []):
                while j < len(mods) and mods[j][0] + mods[j][1] <= start:
                    j += 1
                mod = _module_name(mods[j][2]) if j < len(mods) and \
                    mods[j][0] <= start else ""
                ops.append([_op_name(text), int(start), int(dur), mod])
            if ops:
                out["device"][plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def segments(spans) -> list:
    """The host timeline as [start, end, innermost span] pieces: at
    every instant the span that began last among those still open
    (the innermost, for nested spans) names it."""
    cuts = sorted({x for s in spans for x in (s[1], s[1] + s[2])})
    starts = sorted(spans, key=lambda s: s[1])
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][1] <= a:
            active.append(starts[i])
            i += 1
        active = [s for s in active if s[1] + s[2] > a]
        if active:
            out.append((a, b, max(active, key=lambda s: s[1])[0]))
    return out


def attribute(gaps, spans) -> dict:
    """Seconds of the gaps under each host span (the innermost one at
    each instant); time that no span covers goes to :data:`NO_SPAN`."""
    out = defaultdict(float)
    segs = segments(spans)
    j = 0
    for g0, g1 in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            piece = min(b, g1) - max(a, g0)
            if piece > 0:
                out[name] += piece / 1e9
                covered += piece
            k += 1
        if g1 - g0 > covered:
            out[NO_SPAN] += (g1 - g0 - covered) / 1e9
    return dict(out)


def reduce(trace: dict, kernels: dict) -> dict:
    """``kernels`` maps a kernel name to a regex over "module/op".

    Returns the window's length, the device busy seconds averaged over
    the devices, each kernel's device seconds and event count (summed
    over devices), the ten ops that took most device time, and the ten
    host spans under which the device sat idle longest."""
    win = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    spans = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    busy_s, gaps_by = [], defaultdict(float)
    ksec = {k: 0.0 for k in kernels}
    kcount = {k: 0 for k in kernels}
    op_s = defaultdict(float)
    pats = {k: re.compile(p) for k, p in kernels.items()}
    for ops in trace["device"].values():
        ivs = _clip([(o[1], o[1] + o[2]) for o in ops], lo, hi)
        busy = union(ivs)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for name, sec in attribute(_gaps(busy, lo, hi), spans).items():
            gaps_by[name] += sec / len(trace["device"])
        for name, s, d, mod in ops:
            if s + d <= lo or s >= hi:
                continue
            key = f"{mod}/{name}"
            op_s[key] += d / 1e9
            for k, p in pats.items():
                if p.search(key):
                    ksec[k] += d / 1e9
                    kcount[k] += 1
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_s) / max(len(busy_s), 1),
            "kernel_s": ksec, "kernel_events": kcount,
            "device_ops": top(op_s), "idle_gaps": top(gaps_by)}
