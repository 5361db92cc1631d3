"""A checkout of the benchmark at a size the CPU rehearsals can hold: the
same BENCHMARK.json and data files, with the configurations' rows and the
stream's batches cut down.

A configuration's rows are cut by one rule: its ``"rehearsal"`` entry,
where it states one (``{"n_rows": 600}``; any key it gives replaces the
configuration's), and otherwise ``n_rows`` cut to at most ``MAX_ROWS``.
A run on the chip never reads ``rehearsal``."""
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

MAX_ROWS = 2000
TINY_STREAM = {"batch_rows": 200}
TINY_APPENDS = 4


def make(tmp: pathlib.Path, source: pathlib.Path = ROOT) -> pathlib.Path:
    """Copy the BENCHMARK.json and chipbench's data files of the checkout
    ``source`` under ``tmp``, shrunk; returns the new checkout root."""
    tmp.mkdir(parents=True, exist_ok=True)
    shutil.copy(source / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(source / "chipbench" / sub, tmp / "chipbench" / sub)
    for path in (tmp / "chipbench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update({"n_rows": min(int(cfg["n_rows"]), MAX_ROWS), **cfg.get("rehearsal", {})})
        path.write_text(json.dumps(cfg))
    for path in (tmp / "chipbench" / "traffic").glob("*.json"):
        traffic = json.loads(path.read_text())
        if "stream" in traffic:
            traffic["stream"].update(TINY_STREAM)
            for step in traffic["cycle"]:
                if step["op"] == "append":
                    step["count"] = TINY_APPENDS
        path.write_text(json.dumps(traffic))
    return tmp
