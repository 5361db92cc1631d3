"""segment_append_ms (ms): mean duration of the program's ``stream.append``
span (segment prep, expiry, standing-query refresh) over the window's
appends, from the span recorder of a traced run."""
import statistics


def read(run):
    if run.spans is None:
        return None
    durs = [s["t1"] - s["t0"] for s in run.spans.spans.values()
            if s["name"] == "stream.append" and s["t1"] is not None]
    return statistics.fmean(durs) * 1e3 if durs else None
