"""pack_s (s): mean duration of the program's ``prep.pack`` span (the
N-list pack, ending at its block_until_ready, so device-complete) over the
window's preps, from the span recorder of a traced run."""
import statistics


def read(run):
    if run.spans is None:
        return None
    durs = [s["t1"] - s["t0"] for s in run.spans.spans.values()
            if s["name"] == "prep.pack" and s["t1"] is not None]
    return statistics.fmean(durs) if durs else None
