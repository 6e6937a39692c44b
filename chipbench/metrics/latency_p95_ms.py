"""95th percentile (linear interpolation) of the latencies that
``latency_p50_ms`` takes the median of."""
import numpy as np


def read(record, trace):
    lat = record["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
