"""append_queue_ms (ms): mean admission-and-batching wait of the window's
stream appends, the ``queue_time_s`` of each append's result (submit to
the start of the batch that served it), from the service itself."""
import statistics


def read(run):
    waits = [op.result["queue_time_s"] for op in run.ops
             if op.kind == "append" and op.error is None
             and isinstance(op.result, dict) and "queue_time_s" in op.result]
    return statistics.fmean(waits) * 1e3 if waits else None
