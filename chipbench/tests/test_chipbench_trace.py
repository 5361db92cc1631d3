"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on a hand-made trace with known answers, and on a short trace
recorded on a TPU v5e chip (``data/trace_v5e.json.gz``), checked against
a brute-force timeline."""
import gzip
import json
import pathlib

import numpy as np
import pytest

from chipbench.harness import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"
D = "/device:TPU:0"


def _hand_trace():
    ops = [  # two overlapping ops, a nested one, a kernel, one outside
        [D, "XLA Ops", "fusion.1", 100, 50, {}],
        [D, "XLA Ops", "fusion.2", 120, 60, {}],           # 100-180 busy
        [D, "XLA Ops", "copy.3", 130, 10, {}],             # inside
        [D, "XLA Ops", "nlist_intersect_pallas_es.7", 300, 100,
         {"opcode": "custom-call"}],                       # 300-400
        [D, "XLA Ops", "fusion.9", 950, 200, {}],          # 950-1000 in window
        [D, "XLA Modules", "jit_wave_local(42)", 90, 400, {}],  # a program, not an op
    ]
    host = [
        ["python", "bench:window", 0, 1000],
        ["python", "bench:group.serve", 0, 1000],
        ["python", "bench:mine.reduce", 180, 100],         # covers gap 180-300
        ["worker", "bench:client.make_database", 400, 300],  # covers 400-700
    ]
    return {"device": ops, "host": host}


def test_union_window_and_gaps_by_hand():
    t = _hand_trace()
    busy, window = tr.busy_ns(t)
    assert window == 1000
    assert busy == 80 + 100 + 50
    assert tr.idle_gaps(t) == {D: [(0, 100), (180, 300), (400, 950)]}


def test_kernel_lookup_by_name():
    t = _hand_trace()
    assert tr.kernel_ns(t, ("nlist_intersect_pallas",)) == 100
    assert tr.kernel_ns(t, ("histogram_pallas",)) == 0
    assert tr.kernel_ns(t, ("fusion",)) == 50 + 60 + 50  # clipped to the window


def test_op_names_drop_the_operands():
    hlo = ("%copy.3 = f32[8]{0} copy(f32[8]{0} %nlist_intersect_pallas_es.2)")
    assert tr.op_name(hlo) == "copy.3"
    assert tr._OPCODE.search(hlo.split(" = ", 1)[1]).group(1) == "copy"


def test_gap_attribution_by_hand():
    # each gap goes to the innermost span open at its midpoint: 0-100 (50)
    # to group.serve, 180-300 (240) to mine.reduce, 400-950 (675) to
    # make_database, which is shorter than group.serve
    b = tr.breakdown(_hand_trace())
    assert b["idle_gaps"] == [["client.make_database", 550e-9],
                              ["mine.reduce", 120e-9], ["group.serve", 100e-9]]
    assert b["device_ops"][0] == ["jit_wave_local/nlist_intersect_pallas_es.7", 100e-9]
    assert ["?/fusion.9", 50e-9] in b["device_ops"]  # ran in no recorded program


def _two_plane_trace():
    D1 = "/device:TPU:1"
    ops = [
        [D, "XLA Ops", "fusion.1", 100, 80, {"opcode": "fusion"}],          # 100-180
        [D, "XLA Ops", "all-reduce.1", 300, 100, {"opcode": "all-reduce"}],  # 300-400
        [D1, "XLA Ops", "fusion.1", 0, 600, {"opcode": "fusion"}],          # 0-800 with
        [D1, "Async XLA Ops", "all-reduce-start.2", 600, 20,               # the next
         {"opcode": "all-reduce-start"}],
        [D1, "Async XLA Ops", "all-reduce-done.2", 610, 90,
         {"opcode": "all-reduce-done"}],
        [D1, "XLA Ops", "all-reduce-fusion.3", 700, 100, {"opcode": "fusion"}],  # no collective
        [D1, "XLA Ops", "all-gather.4", 850, 50, {"opcode": "all-gather"}],  # 850-900
    ]
    host = [
        ["python", "bench:window", 0, 1000],
        ["python", "bench:group.serve", 0, 1000],
        ["python", "bench:mine.reduce", 180, 120],
        ["worker", "bench:client.make_database", 400, 400],
    ]
    return {"device": ops, "host": host}


def test_two_planes_are_reduced_per_plane():
    # plane 0 is busy 180 ns, plane 1 850 ns; the union of both planes
    # (800 + 50 ns) would hide plane 0's waits
    t = _two_plane_trace()
    assert tr.busy_ns(t) == ((180 + 850) / 2, 1000)
    assert tr.idle_gaps(t) == {D: [(0, 100), (180, 300), (400, 1000)],
                               "/device:TPU:1": [(800, 850), (900, 1000)]}
    # gaps by the span open at their midpoints, summed, over two planes:
    # make_database 600 (plane 0), mine.reduce 120 (plane 0), group.serve
    # 100 (plane 0) + 50 + 100 (plane 1); in all the window less busy_ns
    idle = tr.breakdown(t)["idle_gaps"]
    assert idle == [["client.make_database", 300e-9], ["group.serve", 125e-9],
                    ["mine.reduce", 60e-9]]
    assert sum(v for _, v in idle) == pytest.approx((1000 - tr.busy_ns(t)[0]) * 1e-9)
    # all-reduce 100 on plane 0; start and done overlapping, 600-700, and
    # all-gather 50 on plane 1; the fusion named after a collective is none
    assert tr.collective_ns(t) == (100 + 150) / 2
    # a kernel's time is summed over the planes: chip-seconds
    assert tr.kernel_ns(t, ("fusion",)) == 80 + 600


def test_a_trace_without_collectives_reads_none():
    assert tr.collective_ns(_hand_trace()) == 0


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _timeline(trace, w0, w1):
    """Brute force: one boolean per nanosecond of the window."""
    busy = np.zeros(w1 - w0, bool)
    for ev in tr.device_ops(trace):
        s, e = max(ev[3], w0), min(ev[3] + ev[4], w1)
        if e > s:
            busy[s - w0:e - w0] = True
    return busy


def test_recorded_trace_union_matches_a_timeline(recorded):
    w0, w1 = tr.window_ns(recorded)
    line = _timeline(recorded, w0, w1)
    busy, window = tr.busy_ns(recorded)
    assert window == w1 - w0
    assert busy == int(line.sum())
    gaps = tr.idle_gaps(recorded)
    assert list(gaps) == [D]
    assert sum(e - s for s, e in gaps[D]) == int((~line).sum())


def test_recorded_trace_names_the_intersect_kernel(recorded):
    ns = tr.kernel_ns(recorded, ("nlist_intersect_pallas",))
    assert ns == sum(min(e[3] + e[4], tr.window_ns(recorded)[1]) - max(e[3], tr.window_ns(recorded)[0])
                     for e in tr.device_ops(recorded)
                     if e[2].startswith("nlist_intersect_pallas") and e[1] == "XLA Ops")
    assert ns > 0
    b = tr.breakdown(recorded)
    assert b["device_ops"] and b["idle_gaps"]
    assert all(isinstance(n, str) and v > 0 for n, v in b["device_ops"] + b["idle_gaps"])


def test_recorded_trace_reads_as_before_on_one_plane(recorded):
    # the values the union over all planes gave before the reduction went
    # per plane: on one plane they are the same
    assert tr.busy_ns(recorded) == (90861427.0, 120000000)
    gaps = tr.idle_gaps(recorded)[D]
    assert len(gaps) == 74 and sum(e - s for s, e in gaps) == 29138573
    assert gaps[:2] == [(3621075003, 3621075005), (3621489558, 3621507882)]
    assert gaps[-2:] == [(3672387859, 3672387861), (3672413140, 3672413145)]
    assert tr.breakdown(recorded) == {
        "device_ops": [
            ["jit_wave_local/nlist_intersect_pallas_es.2", 0.070251087],
            ["jit_wave/nlist_intersect_pallas_es.2", 0.019374109],
            ["jit_wave_local/convert_bitcast_fusion", 0.0004248],
            ["jit_wave/fusion.3", 0.000112893], ["jit_wave_local/fusion.3", 8.9463e-05],
            ["jit_wave/fusion.1", 8.8232e-05], ["jit_wave/fusion", 8.8199e-05],
            ["jit_wave_local/fusion.2", 6.2569e-05], ["jit_wave_local/fusion.1", 6.1837e-05],
            ["jit_wave_local/fusion.5", 2.3829e-05]],
        "idle_gaps": [["client.mine", 0.026550266], ["group.serve", 0.002557304],
                      ["mine.reduce", 3.0971e-05], ["mine.wave", 3.2e-08]]}
    assert tr.collective_ns(recorded) == 0
