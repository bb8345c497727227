"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device operations are the events of the ``XLA Ops`` line on every plane
whose name starts with the device prefix (``/device:TPU:`` on a TPU), each
named by its program (the ``XLA Modules`` event around it) and its HLO
name; on a plane without that line, the events that carry an ``hlo_op``
statistic (how the CPU backend records its ops on host threads).  The traced window runs
from the first start to the last end of the benchmark's top-level host
spans (``bench.round``, ``bench.stream``), which ``jax.profiler.
TraceAnnotation`` writes on the host plane.

Busy time is the union of the device-op intervals inside the window,
averaged over the device planes that ran an op; idle is the rest of the
window.  Each idle gap is named by the innermost ``bench.*`` span and the
innermost host event of that thread that cover its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

HOST_PLANE = "/host:CPU"
TPU_PREFIX = "/device:TPU:"
TOP_SPANS = ("bench.round", "bench.stream")
TOP_N = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _op_name(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...), ...`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def device_ops(pd, prefix: str) -> dict[str, list[tuple[str, float, float]]]:
    """plane name -> [(module:op, start ns, end ns)] of its device ops."""
    out: dict[str, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(prefix):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = []
        if "XLA Ops" in lines:
            modules = sorted((ev.start_ns, ev.end_ns, ev.name.split("(", 1)[0])
                             for ev in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            starts = [m[0] for m in modules]
            for ev in lines["XLA Ops"].events:
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = modules[i][2] if i >= 0 and modules[i][1] >= ev.start_ns \
                    else ""
                ops.append((f"{mod}:{_op_name(ev.name)}", ev.start_ns,
                            ev.end_ns))
        else:
            for ln in lines.values():
                for ev in ln.events:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        ops.append((f"{st.get('hlo_module', '')}:{ev.name}",
                                    ev.start_ns, ev.end_ns))
        if ops:
            out[plane.name] = ops
    return out


def host_events(pd) -> list[tuple[str, str, float, float]]:
    """[(thread line, name, start ns, end ns)] of the host plane."""
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for ln in plane.lines:
            for ev in ln.events:
                out.append((ln.name, ev.name, ev.start_ns, ev.end_ns))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi], sorted."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost_at(events, times: list[float]) -> list:
    """For each time (sorted), the shortest event covering it, or None."""
    evs = sorted(events, key=lambda ev: ev[2])
    out, active, i = [], [], 0
    for t in times:
        while i < len(evs) and evs[i][2] <= t:
            active.append(evs[i])
            i += 1
        active = [ev for ev in active if ev[3] >= t]
        out.append(min(active, key=lambda ev: ev[3] - ev[2], default=None))
    return out


def reduce(pd, prefix: str = TPU_PREFIX, top_spans=TOP_SPANS) -> dict | None:
    """busy_s, window_s, idle_pct and the breakdown of a parsed trace, or
    None where it holds no window or no device op."""
    host = host_events(pd)
    tops = [ev for ev in host if ev[1] in top_spans]
    ops = device_ops(pd, prefix)
    if not tops or not ops:
        return None
    lo = min(ev[2] for ev in tops)
    hi = max(ev[3] for ev in tops)
    window_ns = hi - lo
    per_plane = {p: union([(s, e) for _, s, e in v], lo, hi)
                 for p, v in ops.items()}
    busy_ns = sum(sum(e - s for s, e in b)
                  for b in per_plane.values()) / len(per_plane)
    if busy_ns <= 0:
        return None

    op_time: dict[str, float] = defaultdict(float)
    for v in ops.values():
        for name, s, e in v:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_time[name] += (e - s) / len(ops)

    spans = [ev for ev in host if ev[1].startswith("bench.")]
    thread = tops[0][0]
    on_thread = [ev for ev in host if ev[0] == thread
                 and not ev[1].startswith("bench.")]
    idle: dict[str, float] = defaultdict(float)
    for b in per_plane.values():
        holes = gaps(b, lo, hi)
        mids = [(s + e) / 2 for s, e in holes]
        for (s, e), span, inner in zip(holes, _innermost_at(spans, mids),
                                       _innermost_at(on_thread, mids)):
            name = (span[1] if span else "outside bench spans") + \
                (f" / {inner[1]}" if inner else "")
            idle[name] += (e - s) / len(per_plane)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]

    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(idle)}}


def reduce_file(path: str, prefix: str = TPU_PREFIX) -> dict | None:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), prefix)
