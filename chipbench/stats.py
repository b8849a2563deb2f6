"""Arithmetic shared by the metric readers: percentiles over latency
samples, and device busy time, idle gaps and collectives from a trace."""

from __future__ import annotations

import collections

import numpy as np

from . import trace as tr


def percentile(samples, q: float):
    """The q-th percentile (linear interpolation between order
    statistics), or None without samples."""
    arr = np.asarray(list(samples), dtype=np.float64)
    if arr.size == 0:
        return None
    return float(np.percentile(arr, q))


def busy(trace, device) -> list:
    """Union of the device's operation intervals inside the window."""
    lo, hi = trace.window()
    return tr.union(tr.clip(trace.ops.get(device, []), lo, hi))


def traced_window_s(trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.ops:
        return None
    return sum(tr.length(busy(trace, d)) for d in trace.ops) / len(
        trace.ops) / 1e9


def _host_span_at(trace, t: float) -> str:
    """The innermost benchmark span open at time ``t`` (but the window)."""
    best = None
    for s in trace.spans:
        if s.start <= t < s.end and s.name != "chipbench.window":
            if best is None or s.start >= best.start:
                best = s
    return best.name.split(".", 1)[1] if best is not None else "none"


def breakdown(trace, top: int = 10) -> dict:
    """The device operations that took most time (seconds summed over
    the window and the devices, per operation name), and the longest idle
    gaps of device 0 labelled by the host span open at their middle."""
    if not trace.ops:
        return {"device_ops": [], "idle_gaps": []}
    per_op = op_seconds(trace, lambda e: e.name)
    lo, hi = trace.window()
    d0 = min(trace.ops)
    idle = sorted(tr.gaps(busy(trace, d0), lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v] for n, v in per_op.most_common(top)],
            "idle_gaps": [[_host_span_at(trace, (s + t) / 2), (t - s) / 1e9]
                          for s, t in idle]}


def op_seconds(trace, key) -> collections.Counter:
    """Device seconds inside the window summed over the devices, by
    ``key(event)``; loop and call ops, which span their bodies, are left
    out."""
    lo, hi = trace.window()
    out = collections.Counter()
    for evs in trace.ops.values():
        for e in evs:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s and not tr.is_container(e.code):
                out[key(e)] += (t - s) / 1e9
    return out


def collectives_per_frame(ctx) -> dict:
    """Collective operations run per served frame, by kind, per chip."""
    if ctx.trace is None or not ctx.frames:
        return {}
    lo, hi = ctx.trace.window()
    kinds = collections.Counter()
    for evs in ctx.trace.ops.values():
        for e in evs:
            if tr.is_collective(e.code) and lo <= e.start < hi:
                kinds[tr.COLLECTIVE.match(e.code).group(1)] += 1
    n = len(ctx.trace.ops) * ctx.frames
    return {k: v / n for k, v in sorted(kinds.items())}


def exposed_collective_s(trace, device) -> float:
    """Seconds of the device's collectives during which no other
    operation ran on it, inside the window."""
    lo, hi = trace.window()
    evs = trace.ops.get(device, [])
    coll = tr.union(tr.clip([e for e in evs if tr.is_collective(e.code)],
                            lo, hi))
    other = tr.clip([e for e in evs if not tr.is_collective(e.code)
                     and not tr.is_container(e.code)], lo, hi)
    return tr.length(tr.subtract(coll, other)) / 1e9
