"""The per-layer readers of the program's prep spans, resolve hold, append
queue time and wave slots: exact on results that carry them, silent on
results of a program that lacks them."""
from types import SimpleNamespace

import pytest

from chipbench.harness import cells, runner
from chipbench.harness.client import Op

NEW = ("job2_s", "pack_s", "hold_ms", "append_queue_ms", "wave_yield_pct")


def mine_op(kind, service_stats, stage_times_s, itemsets):
    return Op(kind, result=SimpleNamespace(
        service_stats=service_stats, stage_times_s=stage_times_s, itemsets=itemsets))


def spans(*named):
    return SimpleNamespace(spans={
        i: {"name": n, "t0": t0, "t1": t1} for i, (n, t0, t1) in enumerate(named)})


def record(ops, span_rec=None):
    return runner.RunRecord(ops=ops, setup_s=1.0, peak_bytes=None,
                            device_kind="TPU v5 lite", spans=span_rec)


def test_readers_are_silent_on_a_program_without_the_new_telemetry():
    ops = [mine_op("mine", {"queue_time_s": 0.02}, {"mining_waves": 1.0},
                   {(1,): 9, (1, 2): 5}),
           mine_op("query", {"queue_time_s": 0.02}, {"mining_waves": 1.0}, {(3,): 4}),
           Op("append", result={"segments": 3, "append_s": 0.02})]
    run = record(ops, spans(("group.prep", 0.0, 1.0), ("stream.append", 1.0, 1.5)))
    for name in NEW:
        assert cells.metric_reader(name)(run) is None, name


def test_readers_read_what_the_program_reports():
    ops = [mine_op("mine", {"hold_s": 0.3}, {"wave_slots": 64.0},
                   {(1,): 9, (1, 2): 5, (1, 2, 3): 4}),
           mine_op("query", {"hold_s": 0.1}, {"wave_slots": 16.0},
                   {(3,): 4, (3, 4): 4}),
           Op("append", result={"queue_time_s": 0.02, "hold_s": 0.0}),
           Op("append", result={"queue_time_s": 0.04}),
           Op("append", error=RuntimeError("failed"))]
    run = record(ops, spans(("prep.job2", 1.0, 3.0), ("prep.pack", 3.0, 3.5),
                            ("prep.job2", 5.0, 9.0), ("prep.pack", 9.0, 9.5),
                            ("prep.pack", 10.0, None)))  # still open: left out
    read = {name: cells.metric_reader(name)(run) for name in NEW}
    assert read["job2_s"] == pytest.approx(3.0)
    assert read["pack_s"] == pytest.approx(0.5)
    assert read["hold_ms"] == pytest.approx(200.0)
    assert read["append_queue_ms"] == pytest.approx(30.0)
    assert read["wave_yield_pct"] == pytest.approx(100.0 * 3 / 80)
