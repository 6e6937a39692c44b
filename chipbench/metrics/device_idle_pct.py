"""Share of the traced window in which no operation ran on the device."""
from chipbench import trace as tr


def read(record, trace):
    if trace is None or not trace["devices"]:
        return None
    return tr.idle_pct(trace)
