"""Median latency over all requests due in the window, from each
request's due time to its last solve done (drained requests included)."""
import numpy as np


def read(record, trace):
    lat = record["latencies_s"]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
