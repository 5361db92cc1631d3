"""prep_s (s): mean prep time per mine request: the sum of the engine's
``job1_flist``, ``job2_ppc_pack`` and ``f2_scan`` stage times (each stage
path ends in a device_get, so the sum is device-complete; the split
between the last two is not). 0 for a request served from a cached prep."""
import statistics

STAGES = ("job1_flist", "job2_ppc_pack", "f2_scan")


def read(run):
    preps = [sum(op.result.stage_times_s.get(k, 0.0) for k in STAGES)
             for op in run.ops if op.kind == "mine" and op.error is None]
    return statistics.fmean(preps) if preps else None
