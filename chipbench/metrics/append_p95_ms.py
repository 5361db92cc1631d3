"""append_p95_ms (ms): 95th percentile of the window's stream appends,
each from its append call to its Future resolving (linear interpolation
between order statistics)."""
import numpy as np


def read(run):
    lat = [op.latency_s for op in run.ops if op.kind == "append"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
