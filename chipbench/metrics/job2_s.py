"""job2_s (s): mean duration of the program's ``prep.job2`` span (Job 2:
rank encoding and the PPC-tree, ending at the device_get of its N-list
lengths, so device-complete) over the window's preps, from the span
recorder of a traced run."""
import statistics


def read(run):
    if run.spans is None:
        return None
    durs = [s["t1"] - s["t0"] for s in run.spans.spans.values()
            if s["name"] == "prep.job2" and s["t1"] is not None]
    return statistics.fmean(durs) if durs else None
