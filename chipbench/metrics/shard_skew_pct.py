"""Skew of the device work across the chips of a mesh: 100 × (max ÷ mean
− 1) of each chip's busy time inside the measured window (the union of
its ``XLA Ops`` events, as ``device_idle_pct`` takes it), from the
trace's ``/device:`` planes.  0 where every chip was busy as long.  None
in a run that was not traced, or with fewer than two busy chips."""
from chipbench import spans
from chipbench import trace as tr


def busy_by_device(pd) -> list[float]:
    """Seconds each device that ran an op was busy inside the window."""
    window = spans.host_events(pd, names={tr.WINDOW})
    if not window:
        return []
    lo, hi = window[0][:2]
    out = []
    for plane in pd.planes:
        if not tr._is_device(plane.name):
            continue
        ivs = [iv for line in plane.lines if line.name == tr.OPS_LINE
               for iv in (tr._clip(ev.start_ns, ev.end_ns, lo, hi)
                          for ev in line.events) if iv is not None]
        if ivs:
            out.append(sum(e - s for s, e in tr.union(ivs)) * 1e-9)
    return out


def skew(busy: list[float]) -> float | None:
    if len(busy) < 2 or sum(busy) <= 0.0:
        return None
    return 100.0 * (max(busy) * len(busy) / sum(busy) - 1.0)


def read(record, trace):
    if trace is None or trace["devices"] < 2:
        return None
    path = tr.find_xplane(spans.TRACE_DIR)
    return None if path is None else skew(busy_by_device(tr.load(path)))
