"""exchange_kib (KiB): mean payload each chip adds to the reductions over
the ``data`` axis in one mine request, ``stage_times_s["exchange_bytes"]``
/ 1024: Job 1's histogram, F2's counts and every wave's supports, counted
from shapes where the stages run (a request served from a cached prep
counts its waves only)."""
import statistics


def read(run):
    sizes = [op.result.stage_times_s["exchange_bytes"] for op in run.ops
             if op.kind == "mine" and op.error is None
             and "exchange_bytes" in op.result.stage_times_s]
    return statistics.fmean(sizes) / 1024 if sizes else None
