"""The program's own spans in a traced run, and device-idle time by span.

While the profiler records, the program marks each step of its host work
with a leaf span named ``serve.<layer>.<step>`` (``repro.serve.trace``):
``serve.mux.admit``, ``serve.mux.stack``, ``serve.core.copy_in``,
``serve.core.execute``, ``serve.core.copy_out``, ``serve.mux.finish``.
They are host events of the same ``.xplane.pb`` as the device's ops, on
one clock.

:func:`totals` sums each over the whole trace, which starts after the
warm-up and ends after the drain: the scope of the run's ``requests``
and ``launch_s``.  The metric readers take it from the trace the traced
run left under ``out/trace``; a trace without program spans gives
nothing.  :func:`attribute` splits the window's device-idle time across
the leaves that overlap it, the rest under the benchmark span
(``trace.HOST_SPANS``) that covers it, else ``other``, and names each
idle gap ``<benchmark span>/<leaf>`` by the leaf that covers most of it.

    python3 chipbench/spans.py [trace_dir]

prints both for the newest trace under ``trace_dir`` (default
``chipbench/out/trace``) as one JSON object.
"""
from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402

PREFIX = "serve."
TRACE_DIR = os.path.join(harness.OUT, "trace")

# the harness calls each reader on its own; one parse of a traced run's
# ~100 MB trace serves all six (keyed by file and mtime, one entry)
_cache: dict = {}


def host_events(pd, names=None, prefix=None):
    """(start_ns, end_ns, name) of the host events named in ``names`` or
    starting with ``prefix``, sorted by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if (names is not None and name in names) or \
                        (prefix is not None and name.startswith(prefix)):
                    out.append((ev.start_ns, ev.end_ns, name))
    return sorted(out)


def span_totals(pd) -> dict[str, float]:
    """Seconds per program span over the whole trace."""
    out: dict[str, float] = {}
    for s, e, name in host_events(pd, prefix=PREFIX):
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def totals(trace_dir: str | None = None) -> dict[str, float]:
    """:func:`span_totals` of the newest trace under ``trace_dir``
    (default ``TRACE_DIR``); read once per file for all the readers."""
    path = tr.find_xplane(trace_dir or TRACE_DIR)
    if path is None:
        return {}
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = span_totals(tr.load(path))
    return _cache[key]


def ms_per_request(name: str, record: dict, trace) -> float | None:
    """A span's total in ms per request of the run; None in a run that
    was not traced, or whose trace holds no such span."""
    if trace is None or not record["requests"]:
        return None
    s = totals().get(name)
    return None if s is None else s / record["requests"] * 1e3


def _intersect(a: list, b: list) -> list:
    """Intersections of two sorted lists of disjoint ``(start, end,
    label)`` intervals, as ``(start, end, label_a, label_b)``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e, a[i][2], b[j][2]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _complement(intervals: list, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi)`` outside sorted disjoint ``intervals``."""
    out, at = [], lo
    for s, e in intervals:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


def _busy(pd, lo: float, hi: float) -> list:
    """Busy union of the first device that ran an op in the window (the
    device whose gaps ``trace.reduce`` names)."""
    for plane in pd.planes:
        if not tr._is_device(plane.name):
            continue
        ivs = [iv for line in plane.lines if line.name == tr.OPS_LINE
               for iv in (tr._clip(ev.start_ns, ev.end_ns, lo, hi)
                          for ev in line.events) if iv is not None]
        if ivs:
            return tr.union(ivs)
    return []


def attribute(pd, top: int = 10) -> dict:
    """Device-idle seconds of the window by program leaf span, and the
    window's idle gaps, longest first, named by span."""
    window = host_events(pd, names={tr.WINDOW})
    if not window:
        raise ValueError(f"the trace holds no {tr.WINDOW!r} span")
    lo, hi = window[0][:2]
    idle = [(s, e, i) for i, (s, e) in
            enumerate(_complement(_busy(pd, lo, hi), lo, hi))]
    bench = host_events(pd, names=set(tr.HOST_SPANS))
    leaves = host_events(pd, prefix=PREFIX)
    by_span: dict[str, float] = {}
    gap_leaf = [{} for _ in idle]
    gap_bench = [{} for _ in idle]

    def add(d, k, v):
        d[k] = d.get(k, 0.0) + v

    for s, e, gi, leaf in _intersect(idle, leaves):
        add(by_span, leaf, (e - s) * 1e-9)
        add(gap_leaf[gi], leaf, e - s)
    for s, e, gi, sp in _intersect(idle, bench):
        add(gap_bench[gi], sp, e - s)
    # idle that no leaf covers goes to its benchmark span, else "other"
    outside = _complement(tr.union([(s, e) for s, e, _ in leaves]), lo, hi)
    free = [(s, e, None) for s, e, _, _ in
            _intersect(idle, [(s, e, None) for s, e in outside])]
    rest = sum(e - s for s, e, _ in free)
    for s, e, _, sp in _intersect(free, bench):
        add(by_span, sp, (e - s) * 1e-9)
        rest -= e - s
    if rest > 0:
        add(by_span, "other", rest * 1e-9)
    gaps = []
    for (s, e, gi) in idle:
        name = max(gap_bench[gi].items(), key=lambda kv: kv[1])[0] \
            if gap_bench[gi] else "other"
        if gap_leaf[gi]:
            name += "/" + max(gap_leaf[gi].items(),
                              key=lambda kv: kv[1])[0]
        gaps.append((name, (e - s) * 1e-9))
    idle_s = sum(e - s for s, e, _ in idle) * 1e-9
    in_leaves = sum(v for k, v in by_span.items() if k.startswith(PREFIX))
    return {"idle_s": idle_s, "idle_by_span": by_span,
            "idle_in_leaves_pct": 100.0 * in_leaves / idle_s
            if idle_s > 0 else None,
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top]}


def main(argv: list[str]) -> None:
    path = tr.find_xplane(argv[0] if argv else TRACE_DIR)
    if path is None:
        sys.exit(f"no .xplane.pb under {argv[0] if argv else TRACE_DIR}")
    pd = tr.load(path)
    print(json.dumps({"trace": path, "bytes": os.path.getsize(path),
                      "spans": span_totals(pd), **attribute(pd)}))


if __name__ == "__main__":
    main(sys.argv[1:])
