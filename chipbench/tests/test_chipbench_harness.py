"""CPU rehearsal of the harness: every cell end to end at a tiny size."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench.harness import cells, data, reference, runner
from chipbench.tests import rehearse, tinyroot

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the rehearsals leave the process's compile-cache settings alone
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


def run_cell(root, name, *, trace=False, seed=2**31 + 7):
    """One rehearsal of a cell: in this process on its one CPU device, or,
    for a cell on more chips, in a child with as many (``rehearse``)."""
    chips = cells.find_cell(name, cells.load_benchmark(root), root / "chipbench").chips
    if chips > 1:
        p = rehearse.run(root, name, seed, trace, chips)
        assert p.returncode == 0, p.stderr[-4000:]
        return json.loads(p.stdout.strip().splitlines()[-1])
    out = io.StringIO()
    rc = runner.main(name, seed, 0.2, trace, t_process=time.perf_counter(),
                     require_tpu=False, root=root, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def check_result(root, name, trace, res):
    """What every cell's rehearsal must show."""
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
    assert res["device"]["count"] == cell.chips
    if trace:
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(root, name, trace):
    check_result(root, name, trace, run_cell(root, name, trace=trace))


SMALL_SWEEP = {"warmup": [{"op": "mine", "database": "resident", "min_sups": [0.3, 0.2], "max_k": 4}],
               "cycle": [{"op": "mine", "database": "resident", "min_sups": [0.3, 0.2], "max_k": 4}]}


def test_a_new_cell_is_new_files_and_a_workloads_entry(root, tmp_path):
    new = tinyroot.make(tmp_path)
    cfg = json.loads((new / "chipbench/configs/mushroom.json").read_text())
    cfg.update(name="mushroom_small", n_rows=300)
    (new / "chipbench/configs/mushroom_small.json").write_text(json.dumps(cfg))
    (new / "chipbench/traffic/resident_sweep_0.3-0.2.json").write_text(json.dumps(SMALL_SWEEP))
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mushroom_small.sweep", "config": "mushroom_small",
                               "traffic": "resident_sweep_0.3-0.2", "chips": 1, "why": "t"})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(new, "mushroom_small.sweep")
    assert res["correct"] is True and res["attempted"] % 2 == 0


def source_checkout(tmp, configs=(), traffic=(), workloads=()):
    """This tree's BENCHMARK.json and chipbench data files under ``tmp``,
    with new configurations and traffic mixes ({name: dict}) and
    ``workloads`` entries added: a checkout that differs from this one only
    by new files and entries."""
    tmp.mkdir(parents=True, exist_ok=True)
    shutil.copy(tinyroot.ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(tinyroot.BENCH / sub, tmp / "chipbench" / sub)
    for sub, files in (("configs", configs), ("traffic", traffic)):
        for name, content in dict(files).items():
            (tmp / "chipbench" / sub / f"{name}.json").write_text(json.dumps(content))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["workloads"] += list(workloads)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def test_a_new_configuration_is_cut_by_the_rehearsal_rule(tmp_path):
    cfg = json.loads((tinyroot.BENCH / "configs/mushroom.json").read_text())
    del cfg["rehearsal"]
    cfg["name"] = "mushroom_wide"
    src = source_checkout(
        tmp_path / "src", configs={"mushroom_wide": cfg},
        traffic={"resident_sweep_0.3-0.2": SMALL_SWEEP},
        workloads=[{"name": "mushroom_wide.sweep", "config": "mushroom_wide",
                    "traffic": "resident_sweep_0.3-0.2", "chips": 1, "why": "t"}])
    new = tinyroot.make(tmp_path / "tiny", src)
    rows = {p.stem: json.loads(p.read_text())["n_rows"]
            for p in (new / "chipbench/configs").glob("*.json")}
    assert rows == {"kosarak": 3000, "mushroom": 600, "mushroom_wide": tinyroot.MAX_ROWS}
    stream = json.loads((new / "chipbench/traffic/window3_every16_sup0.13.json").read_text())
    assert stream["stream"]["batch_rows"] == 200
    assert {s["count"] for s in stream["cycle"] if s["op"] == "append"} == {tinyroot.TINY_APPENDS}
    check_result(new, "mushroom_wide.sweep", False, run_cell(new, "mushroom_wide.sweep"))


X4 = "kosarak.oneshot.x4"


@pytest.fixture(scope="module")
def x4_root(tmp_path_factory):
    """A checkout with one new configuration (kosarak on a 4x1 mesh of four
    chips) and one four-chip ``workloads`` entry, cut for rehearsal."""
    cfg = json.loads((tinyroot.BENCH / "configs/kosarak.json").read_text())
    cfg.update(chips=4, mesh=[4, 1])
    tmp = tmp_path_factory.mktemp("x4")
    src = source_checkout(tmp / "src", configs={"kosarak_x4": cfg}, workloads=[
        {"name": X4, "config": "kosarak_x4", "traffic": "fresh_db_sup0.01", "chips": 4,
         "why": "t"}])
    return tinyroot.make(tmp / "tiny", src)


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_a_four_chip_cell_is_new_files_and_a_workloads_entry(x4_root, trace):
    check_result(x4_root, X4, trace, run_cell(x4_root, X4, trace=trace))


def test_a_four_chip_rehearsal_serves_from_four_data_shards(x4_root):
    p = rehearse.run(x4_root, X4, 2**31 + 17, False, 4)
    assert p.returncode == 0, p.stderr[-4000:]
    check_result(x4_root, X4, False, json.loads(p.stdout.strip().splitlines()[-1]))
    assert "mesh: {'data': 4, 'model': 1}" in p.stderr
    shards = [json.loads(line.split(":", 1)[1]) for line in p.stderr.splitlines()
              if line.startswith("data shards:")]
    assert shards, p.stderr[-4000:]
    for counts in shards:  # every prepared database: four shards, none empty
        assert len(counts) == 4 and min(counts) > 0


@pytest.mark.parametrize("mesh,chips", [([2, 1], 4), ([4, 1], 1), ([4], 4)],
                         ids=["smaller_than_the_cell", "not_the_configurations", "one_axis"])
def test_a_mesh_that_does_not_fit_the_chips_is_an_error(tmp_path, mesh, chips):
    cfg = json.loads((tinyroot.BENCH / "configs/mushroom.json").read_text())
    cfg.update(chips=chips, mesh=mesh)
    root = source_checkout(tmp_path, configs={"mushroom_mesh": cfg}, workloads=[
        {"name": "mushroom_mesh.sweep", "config": "mushroom_mesh",
         "traffic": "resident_sweep_0.20-0.13", "chips": 4, "why": "t"}])
    out = io.StringIO()
    with pytest.raises(ValueError, match="mesh"):
        runner.main("mushroom_mesh.sweep", 3, 0.2, False, t_process=time.perf_counter(),
                    require_tpu=False, root=root, out=out)
    assert out.getvalue() == ""


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
