"""CPU rehearsal of the harness: every cell end to end at a tiny size."""
import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench.harness import cells, data, reference, runner
from chipbench.tests import tinyroot

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the rehearsals leave the process's compile-cache settings alone
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


def run_cell(root, name, *, trace=False, seed=2**31 + 7):
    out = io.StringIO()
    rc = runner.main(name, seed, 0.2, trace, t_process=time.perf_counter(),
                     require_tpu=False, root=root, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(root, name, trace):
    res = run_cell(root, name, trace=trace)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    cell = cells.find_cell(name, cells.load_benchmark(root), root / "chipbench")
    wanted = cell.per_layer if trace else cell.end_to_end
    # on the CPU there is no device memory counter and no device plane:
    # those readers find nothing and their metrics are left out
    cpu_silent = {"hbm_peak_gib", "device_idle", "intersect_roofline"}
    assert {m["name"] for m in wanted} - cpu_silent == set(res["metrics"])
    for m in wanted:
        if m["name"] in res["metrics"]:
            assert res["metrics"][m["name"]]["unit"] == m["unit"]
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert res["device"]["window_s"] > 0


def test_a_new_cell_is_new_files_and_a_workloads_entry(root, tmp_path):
    new = tinyroot.make(tmp_path)
    cfg = json.loads((new / "chipbench/configs/mushroom.json").read_text())
    cfg.update(name="mushroom_small", n_rows=300)
    (new / "chipbench/configs/mushroom_small.json").write_text(json.dumps(cfg))
    (new / "chipbench/traffic/resident_sweep_0.3-0.2.json").write_text(json.dumps({
        "warmup": [{"op": "mine", "database": "resident", "min_sups": [0.3, 0.2], "max_k": 4}],
        "cycle": [{"op": "mine", "database": "resident", "min_sups": [0.3, 0.2], "max_k": 4}]}))
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mushroom_small.sweep", "config": "mushroom_small",
                               "traffic": "resident_sweep_0.3-0.2", "chips": 1, "why": "t"})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(new, "mushroom_small.sweep")
    assert res["correct"] is True and res["attempted"] % 2 == 0


def test_unknown_cell_is_an_error(root):
    with pytest.raises(KeyError):
        cells.find_cell("nosuch.cell", cells.load_benchmark(root), root / "chipbench")


def test_run_py_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(tinyroot.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_relabelled_databases_keep_the_answer():
    cfg = json.loads((tinyroot.BENCH / "configs/kosarak.json").read_text())
    base = data.sparse_rows({**cfg, "n_items": 300}, np.random.default_rng(5), 2000)
    count = reference.min_count(0.01, len(base))
    want = reference.frequent_itemsets(base, 300, count, 6)
    assert any(len(s) >= 3 for s in want)
    for i in range(3):
        rows, item_map = data.relabelled(base, 300, np.random.default_rng([9, i]))
        assert not np.array_equal(rows, base)
        got = reference.frequent_itemsets(rows, 300, count, 6)
        assert got == data.relabel_answer(want, item_map)


def test_readers_find_nothing_in_an_empty_run():
    bench = cells.load_benchmark()
    run = runner.RunRecord(ops=[], setup_s=1.0, peak_bytes=None,
                           device_kind="TPU v5 lite")
    for m in bench["end_to_end"] + bench["per_layer"]:
        value = cells.metric_reader(m["name"])(run)
        assert value is None or m["name"] == "setup_s"


@pytest.mark.parametrize("name", ["kosarak", "mushroom"])
def test_configs_realize_the_figures_they_state(name):
    cfg = json.loads((tinyroot.BENCH / f"configs/{name}.json").read_text())
    rows = data.base_database(cfg)
    lens = (rows >= 0).sum(axis=1)
    real = cfg["realized"]
    assert rows.shape == (cfg["n_rows"], cfg["max_len"])
    assert round(float(lens.mean()), 4) == real["avg_len"]
    assert int(lens.max()) == real["max_len"]
    assert len(np.unique(rows[rows >= 0])) == real["items_occurring"]


def test_stream_batches_are_new_content_and_the_same_work():
    from chipbench.harness.client import ClosedLoopClient
    from chipbench.harness.control import _NoService

    cell = cells.find_cell("mushroom.stream", cells.load_benchmark())
    traffic = {**cell.traffic, "stream": {**cell.traffic["stream"], "batch_rows": 100}}
    runs = []
    for seed in (5, 2**31 + 3):
        client = ClosedLoopClient(_NoService(), cell.config, traffic, seed, None)
        client.warm_up()
        client.run_steps(traffic["cycle"])
        runs.append([client.stream_rows(i) for i in client._appended])
    for batches in runs:  # no batch repeats within a run
        assert len({b.tobytes() for b in batches}) == len(batches)
    n_items = cell.config["n_items"]
    for a, b in zip(*runs):  # other labels, the same supports
        assert not np.array_equal(a, b)
        assert np.array_equal(np.sort(np.bincount(a.ravel(), minlength=n_items)),
                              np.sort(np.bincount(b.ravel(), minlength=n_items)))
