"""device_idle (%): share of the traced window in which no op ran on the
device: 100 * (1 - busy / window), busy the union of a device's op
intervals, averaged over the devices (harness/trace_reduce.py)."""
from chipbench.harness import trace_reduce


def read(run):
    if run.trace is None:
        return None
    busy, window = trace_reduce.busy_ns(run.trace)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / window)
