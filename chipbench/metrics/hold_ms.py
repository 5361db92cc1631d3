"""hold_ms (ms): mean resolve hold of the window's mine requests and
stream queries, ``service_stats["hold_s"]``: from the request's own answer
being complete to its Future resolving, the time it waits for the rest of
its batch to be served, from the service itself."""
import statistics


def read(run):
    holds = [op.result.service_stats["hold_s"] for op in run.ops
             if op.kind in ("mine", "query") and op.error is None
             and "hold_s" in op.result.service_stats]
    return statistics.fmean(holds) * 1e3 if holds else None
