"""mine_s (s): mean latency of the window's mine requests (stream queries
included), each from its submit/sweep call to its Future resolving."""
import statistics


def read(run):
    lat = [op.latency_s for op in run.ops if op.kind in ("mine", "query")]
    return statistics.fmean(lat) if lat else None
