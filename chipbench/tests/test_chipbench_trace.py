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
    assert tr.idle_gaps(t) == [(0, 100), (180, 300), (400, 950)]


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
    assert sum(e - s for s, e in gaps) == int((~line).sum())


def test_recorded_trace_names_the_intersect_kernel(recorded):
    ns = tr.kernel_ns(recorded, ("nlist_intersect_pallas",))
    assert ns == sum(min(e[3] + e[4], tr.window_ns(recorded)[1]) - max(e[3], tr.window_ns(recorded)[0])
                     for e in tr.device_ops(recorded)
                     if e[2].startswith("nlist_intersect_pallas") and e[1] == "XLA Ops")
    assert ns > 0
    b = tr.breakdown(recorded)
    assert b["device_ops"] and b["idle_gaps"]
    assert all(isinstance(n, str) and v > 0 for n, v in b["device_ops"] + b["idle_gaps"])
