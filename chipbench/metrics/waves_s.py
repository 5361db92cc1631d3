"""waves_s (s): mean time of the k>=2 wave loop per mine request or
stream query, ``stage_times_s["mining_waves"]`` (it ends in a blocking
device_get)."""
import statistics


def read(run):
    waves = [op.result.stage_times_s["mining_waves"] for op in run.ops
             if op.kind in ("mine", "query") and op.error is None
             and "mining_waves" in op.result.stage_times_s]
    return statistics.fmean(waves) if waves else None
