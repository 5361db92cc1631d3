"""A checkout of the benchmark at a size the CPU rehearsals can hold: the
same BENCHMARK.json and data files, with the configurations' rows and the
stream's batches cut down."""
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_ROWS = {"kosarak": 3000, "mushroom": 600}
TINY_STREAM = {"batch_rows": 200}
TINY_APPENDS = 4


def make(tmp: pathlib.Path) -> pathlib.Path:
    """Copy BENCHMARK.json and chipbench's data files under ``tmp``,
    shrunk; returns the new checkout root."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, tmp / "chipbench" / sub)
    for path in (tmp / "chipbench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["n_rows"] = TINY_ROWS[cfg["name"]]
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
