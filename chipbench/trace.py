"""Reduce a JAX profiler trace (``.xplane.pb``) of one measured window.

The benchmark marks the window and what the host does inside it with
``jax.profiler.TraceAnnotation`` spans (``WINDOW`` and ``HOST_SPANS``).
:func:`reduce` reads the trace through ``jax.profiler.ProfileData`` and
returns, for the window:

  busy_s     the union of the intervals in which an operation ran on a
             device (the ``XLA Ops`` line of each ``/device:`` plane),
             averaged over the devices that ran any
  ops        per operation (by :func:`short_name`): device seconds, event
             count, and its full name and string stats (kernels are
             matched against these)
  gaps       each idle interval of the first busy device, named by the
             host span that overlaps it most (``other`` where none does)

Times in the trace are nanoseconds on one clock for host and device.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "window"
HOST_SPANS = ("generate", "submit", "poll", "drain", "wait")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _overlap(a: tuple, b: tuple) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _stat_strings(event) -> list[str]:
    try:
        return [str(v) for _, v in event.stats if isinstance(v, str)]
    except Exception:       # noqa: BLE001 -- stats of unknown types
        return []


_HLO = re.compile(r"^%?(\S+) = .*? ([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """``%copy.1 = f32[...] copy(...)`` -> ``copy.1 copy``; a custom call
    also names its target.  Names that are not HLO text stay as they are."""
    m = _HLO.match(name)
    if m is None:
        return name
    t = _TARGET.search(name)
    return " ".join([m.group(1), m.group(2)] + ([t.group(1)] if t else []))


def reduce(pd) -> dict:
    """Summary of the window marked by the ``WINDOW`` span (seconds)."""
    spans: list[tuple[str, float, float]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif _is_device(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = window
    ops: dict[str, dict] = {}
    busy_per_device = []
    first_busy = None
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if iv is None:
                    continue
                intervals.append(iv)
                op = ops.setdefault(short_name(ev.name), {
                    "s": 0.0, "count": 0,
                    "stats": [ev.name] + _stat_strings(ev)})
                op["s"] += (iv[1] - iv[0]) * 1e-9
                op["count"] += 1
        if intervals:
            merged = union(intervals)
            busy_per_device.append(sum(e - s for s, e in merged))
            if first_busy is None:
                first_busy = merged
    gaps = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name, best = "other", 0.0
                for sp, ss, se in spans:
                    ov = _overlap((s, e), (ss, se))
                    if ov > best:
                        name, best = sp, ov
                gaps.append((name, (e - s) * 1e-9))
    busy = (sum(busy_per_device) / len(busy_per_device)
            if busy_per_device else 0.0)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "devices": len(busy_per_device), "ops": ops, "gaps": gaps}


def idle_pct(summary: dict) -> float:
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def kernel(summary: dict, match) -> tuple[float, int]:
    """Device seconds and events of the operations for which
    ``match(name, stat_strings)`` holds."""
    s, n = 0.0, 0
    for name, op in summary["ops"].items():
        if match(name, op["stats"]):
            s += op["s"]
            n += op["count"]
    return s, n


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(((n, o["s"]) for n, o in summary["ops"].items()),
                 key=lambda x: -x[1])[:top]
    gaps = sorted(summary["gaps"], key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}

