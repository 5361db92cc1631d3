"""queue_ms (ms): mean admission-and-batching wait of the window's mine
requests and stream queries, ``service_stats["queue_time_s"]`` (submit to
the start of the batch that served it), from the service itself."""
import statistics


def read(run):
    waits = [op.result.service_stats["queue_time_s"] for op in run.ops
             if op.kind in ("mine", "query") and op.error is None
             and "queue_time_s" in op.result.service_stats]
    return statistics.fmean(waits) * 1e3 if waits else None
