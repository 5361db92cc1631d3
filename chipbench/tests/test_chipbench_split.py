"""The four-chip cell's telemetry and its check: the readers of the data
split's span and counters, exact on results that carry them and silent on
a program that lacks them; and the exchange left out, which makes
``correct`` false."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from chipbench.harness import cells, runner
from chipbench.harness.client import Op
from chipbench.tests import rehearse, tinyroot

NEW = ("split_ms", "nlist_fill_pct", "exchange_kib")
X4 = "kosarak.oneshot.x4"


def mine_op(kind, stage_times_s):
    return Op(kind, result=SimpleNamespace(service_stats={}, stage_times_s=stage_times_s,
                                           itemsets={}))


def record(ops, named=()):
    spans = SimpleNamespace(spans={
        i: {"name": n, "t0": t0, "t1": t1} for i, (n, t0, t1) in enumerate(named)})
    return runner.RunRecord(ops=ops, setup_s=1.0, peak_bytes=None,
                            device_kind="TPU v5 lite", spans=spans)


def test_readers_are_silent_on_a_program_without_the_split_telemetry():
    ops = [mine_op("mine", {"mining_waves": 1.0, "wave_slots": 64.0}),
           mine_op("query", {"mining_waves": 1.0})]
    run = record(ops, [("prep.job1", 0.0, 1.0), ("prep.job2", 1.0, 2.0)])
    for name in NEW:
        assert cells.metric_reader(name)(run) is None, name


def test_readers_read_what_the_program_reports():
    ops = [mine_op("mine", {"nlist_nodes": 30.0, "nlist_slots": 100.0,
                            "exchange_bytes": 4096.0}),
           mine_op("mine", {"nlist_nodes": 10.0, "nlist_slots": 100.0,
                            "exchange_bytes": 1024.0}),
           # a stream query is not a mine request
           mine_op("query", {"nlist_nodes": 1.0, "nlist_slots": 1000.0,
                             "exchange_bytes": 1e9}),
           Op("mine", error=RuntimeError("failed"))]
    run = record(ops, [("prep.split", 1.0, 1.002), ("prep.job1", 1.002, 2.0),
                       ("prep.split", 5.0, 5.004), ("prep.split", 9.0, None)])
    read = {name: cells.metric_reader(name)(run) for name in NEW}
    assert read["split_ms"] == pytest.approx(3.0)
    assert read["nlist_fill_pct"] == pytest.approx(20.0)
    assert read["exchange_kib"] == pytest.approx(2.5)


def test_the_cell_is_kosaraks_rows_on_a_four_by_one_mesh():
    bench = cells.load_benchmark()
    x4 = cells.find_cell(X4, bench)
    one = cells.find_cell("kosarak.oneshot", bench)
    assert (x4.chips, x4.mesh) == (4, (4, 1))
    assert x4.traffic == one.traffic
    differs = {k for k in x4.config.keys() | one.config.keys()
               if x4.config.get(k) != one.config.get(k)}
    assert differs == {"chips", "mesh", "deployment"}


# the support psum over `data` left out of both wave programs: each chip's
# partial support is taken as the whole
_NO_EXCHANGE = textwrap.dedent(
    """
    import sys
    import jax

    psum, left_out = jax.lax.psum, []

    def no_exchange(x, axis_name, **kwargs):
        if "<locals>.wave" in sys._getframe(1).f_code.co_qualname:
            left_out.append(axis_name)
            return x
        return psum(x, axis_name, **kwargs)

    jax.lax.psum = no_exchange
    from chipbench.tests import rehearse

    rc = rehearse.main(sys.argv[1:])
    print(f"support psums left out: {len(left_out)}", file=sys.stderr)
    sys.exit(rc)
    """
)


def test_a_four_chip_cell_without_the_exchange_is_not_correct(tmp_path):
    root = tinyroot.make(tmp_path)
    flags = os.environ.get("XLA_FLAGS", "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=4".strip(),
           "PYTHONPATH": os.pathsep.join([str(rehearse.ROOT), str(rehearse.ROOT / "src")])}
    p = subprocess.run(
        [sys.executable, "-c", _NO_EXCHANGE, str(root), X4, str(2**31 + 11), "0", "4"],
        cwd=rehearse.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "mesh: {'data': 4, 'model': 1}" in p.stderr
    left_out = int(p.stderr.rsplit("support psums left out: ", 1)[1].split()[0])
    assert left_out > 0
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0
