"""Reduction of a profiler trace to device busy time and a breakdown.

The traced window is the host span ``tick`` (one measured tick).  Device
busy time is the union of the intervals in which an operation ran on a
device, inside that window, averaged over the devices that ran any.  The
breakdown lists the operations that took most device time (self time: less
the ops nested in them) and the longest idle gaps, each named by the
innermost host span at its middle.  Only the reduction is kept, never the
trace file.
"""
from __future__ import annotations

import glob
import os

TOP = 10
WINDOW_SPAN = "tick"


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events, lo: float, hi: float):
    """(op, seconds) of each event inside [lo, hi], less the time of the
    events nested in it (a while loop holds its body's ops)."""
    stack: list[list] = []          # [name, end, clipped duration, child]
    out = []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            n, _, d, child = stack.pop()
            out.append((n, d - child))
        d = max(0.0, min(b, hi) - max(a, lo))
        if stack:
            stack[-1][3] += d
        stack.append([name, b, d, 0.0])
    out += [(n, d - child) for n, _, d, child in stack]
    return [(n, d) for n, d in out if d > 0]


def _innermost(spans, t: float) -> str:
    """Name of the shortest host span that holds time t."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "host: outside any span"


def reduce(device: dict, host: list, top: int = TOP) -> dict:
    """``device``: device name -> [(op, start_s, end_s)]; ``host``:
    [(span, start_s, end_s)] on the same clock, one of them the window span.

    Returns busy_s (mean over devices), window_s, device_ops (op name ->
    device seconds, top entries) and idle_gaps (host span -> seconds of the
    longest gaps).
    """
    windows = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    busy, gaps, ops = [], [], {}
    for events in device.values():
        merged = union(((a, b) for _, a, b in events), lo, hi)
        if not merged:
            continue
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, d in self_times(events, lo, hi):
            ops[name] = ops.get(name, 0.0) + d
    inner = [s for s in host if s[0] != WINDOW_SPAN]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": hi - lo,
        "device_ops": [[n, s] for n, s in sorted(ops.items(),
                                                 key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_innermost(inner, 0.5 * (a + b)), b - a]
                      for a, b in gaps[:top]],
    }


def read_xplane(path: str, host_names) -> tuple[dict, list]:
    """Device ops and the named host spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            # An op's event name is its HLO text; keep the instruction name.
            device[plane.name] = [(ev.name.split(" = ", 1)[0],
                                   ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                                  for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                     for ln in plane.lines for ev in ln.events
                     if ev.name in host_names]
    return device, host


def reduce_dir(trace_dir: str, host_names) -> dict:
    """Reduce the one trace the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, got {paths}")
    device, host = read_xplane(paths[0], set(host_names) | {WINDOW_SPAN})
    return reduce(device, host)
