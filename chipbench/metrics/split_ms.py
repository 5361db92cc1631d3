"""split_ms (ms): mean duration of the program's ``prep.split`` span (the
data split: rows padded to a multiple of the data shards and each shard's
block placed on its chip, ending when the rows are there) over the
window's preps, from the span recorder of a traced run."""
import statistics


def read(run):
    if run.spans is None:
        return None
    durs = [s["t1"] - s["t0"] for s in run.spans.spans.values()
            if s["name"] == "prep.split" and s["t1"] is not None]
    return statistics.fmean(durs) * 1e3 if durs else None
