"""Telemetry (repro.mining.telemetry): histograms, traces, the periodic
emitter, and the wiring through the serving stack.

Anchors, per the PR acceptance criteria:
  - ``LatencyHistogram`` keeps exact counts under concurrency, merges
    bucket-for-bucket, and its quantile estimates stay inside the bucket
    that contains the true quantile (deterministic versions here; the
    hypothesis sweeps live in test_telemetry_properties.py);
  - ``TraceRecorder`` nests spans implicitly per thread and explicitly
    across threads, exports valid Chrome trace events, and costs one
    global read when detached;
  - ``StatsEmitter`` keeps ticking through chaos drops and sink errors —
    a lost emit is a counted line, never an exception;
  - after a multi-request serve, ``service.stats()['histograms']``
    reports populated queue-wait / prep / mine / request histograms, and
    a distributed mine records per-worker wave RPC histograms.
"""
import io
import json
import math
import threading
import time

import numpy as np
import pytest

from repro.data.synth import random_db
from repro.fault.failures import ChaosInjector, installed
from repro.mining import MineSpec, MiningEngine
from repro.mining.telemetry import (
    DEFAULT_EDGES, SCHEMA_VERSION, LatencyHistogram, Registry, StatsEmitter,
    TraceRecorder, trace,
)


def _true_quantile(vals, q):
    k = min(len(vals), max(1, math.ceil(q * len(vals))))
    return sorted(vals)[k - 1]


# ------------------------------------------------------------- histogram
def test_record_exact_counts_and_bucket_placement():
    h = LatencyHistogram()
    h.record(0.0)        # bucket 0 (v <= first edge)
    h.record(1e-6)       # still bucket 0 (edges are upper bounds)
    h.record(1.5e-6)     # bucket 1
    h.record(10.0)       # mid-range
    h.record(1e9)        # above the last edge -> overflow bucket
    assert h.n == 5 and sum(h.counts) == 5
    assert h.counts[0] == 2 and h.counts[1] == 1
    assert h.counts[-1] == 1  # overflow
    assert h.vmin == 0.0 and h.vmax == 1e9
    assert h.total == pytest.approx(10.0 + 1e9 + 2.5e-6)


def test_negative_and_nan_clamp_to_zero():
    h = LatencyHistogram()
    h.record(-3.0)
    h.record(float("nan"))
    assert h.n == 2 and h.counts[0] == 2
    assert h.vmin == 0.0 and h.vmax == 0.0 and h.total == 0.0


def test_empty_histogram_is_well_defined():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    assert h.quantile_bounds(0.99) == (0.0, 0.0)
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["buckets"] == {}
    assert snap["min_s"] == 0.0 and snap["max_s"] == 0.0


def test_quantile_estimate_bounded_by_bucket_and_extremes():
    vals = [3e-6, 5e-6, 5e-6, 2e-4, 1e-3, 1e-3, 4e-2, 0.3, 0.3, 7.0]
    h = LatencyHistogram()
    for v in vals:
        h.record(v)
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        lo, hi = h.quantile_bounds(q)
        true = _true_quantile(vals, q)
        est = h.quantile(q)
        assert lo <= true <= hi
        assert lo <= est <= hi
        assert h.vmin <= est <= h.vmax
    # monotone in q (bucket index can only move right)
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert qs == sorted(qs)


def test_merge_is_exact_and_order_free():
    rng = np.random.default_rng(7)
    parts = [rng.uniform(0, 2.0, 40) for _ in range(3)]
    hs = []
    for p in parts:
        h = LatencyHistogram()
        for v in p:
            h.record(float(v))
        hs.append(h)
    whole = LatencyHistogram()
    for v in np.concatenate(parts):
        whole.record(float(v))
    ab_c = hs[0].copy().merge(hs[1]).merge(hs[2])
    a_bc = hs[0].copy().merge(hs[1].copy().merge(hs[2]))
    ba = hs[1].copy().merge(hs[0])
    for m in (ab_c, a_bc):
        assert m.counts == whole.counts and m.n == whole.n
        assert m.vmin == whole.vmin and m.vmax == whole.vmax
        assert m.total == pytest.approx(whole.total)
    assert ba.counts == hs[0].copy().merge(hs[1]).counts


def test_merge_rejects_mismatched_edges():
    with pytest.raises(ValueError):
        LatencyHistogram().merge(LatencyHistogram(edges=(1.0, 2.0)))
    with pytest.raises(ValueError):
        LatencyHistogram(edges=(2.0, 1.0))  # must be strictly increasing


def test_concurrent_records_and_merges_lose_nothing():
    target = LatencyHistogram()
    n_threads, per_thread = 8, 4000

    def hammer(tid):
        local = LatencyHistogram()
        for i in range(per_thread):
            v = (tid * per_thread + i) % 997 * 1e-5
            if i % 2:
                target.record(v)  # direct contended records
            else:
                local.record(v)  # plus a merged batch
        target.merge(local)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert target.n == n_threads * per_thread
    assert sum(target.counts) == target.n
    assert target.vmin == 0.0 and target.vmax == 996 * 1e-5


def test_registry_get_or_create_and_snapshot_shape():
    r = Registry()
    assert r.histogram("a.b_s") is r.histogram("a.b_s")
    r.histogram("a.b_s").record(0.01)
    r.counter("c").inc(3)
    r.gauge("g").set(2.5)
    r.gauge("g").add(-0.5)
    snap = r.snapshot()
    assert snap["schema"] == SCHEMA_VERSION
    assert snap["histograms"]["a.b_s"]["count"] == 1
    assert snap["counters"] == {"c": 3}
    assert snap["gauges"] == {"g": 2.0}
    json.dumps(snap)  # the whole snapshot must be JSON-clean


# ----------------------------------------------------------------- trace
def test_span_is_noop_when_detached():
    assert trace.active() is None
    with trace.span("anything", k=2) as sid:
        assert sid is None  # shared null context manager


def test_spans_nest_implicitly_and_export_chrome():
    rec = TraceRecorder()
    with trace.attached(rec):
        with rec.span("request", kind="mine") as root:
            with rec.span("group.serve"):
                with rec.span("mine.wave", k=2):
                    pass
                with rec.span("mine.wave", k=3):
                    pass
        rec.add("admission.wait", rec.epoch, rec.epoch + 0.001, parent=root)
    assert trace.active() is None  # detached on exit
    roots = rec.to_json()
    assert len(roots) == 1 and roots[0]["name"] == "request"
    serve = next(c for c in roots[0]["children"] if c["name"] == "group.serve")
    assert [c["args"]["k"] for c in serve["children"]] == [2, 3]
    wait = next(c for c in roots[0]["children"] if c["name"] == "admission.wait")
    assert wait["dur_s"] == pytest.approx(0.001)
    events = rec.to_chrome()
    assert len(events) == len(rec) == 5
    for ev in events:
        assert ev["ph"] == "X" and ev["ts"] >= 0 and ev["dur"] >= 0
        assert ev["name"] and "span_id" in ev["args"]


def test_explicit_parent_crosses_threads():
    rec = TraceRecorder()
    root = rec.open("request")

    def worker():
        with rec.span("host.mine", parent=root):
            time.sleep(0.001)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    rec.close(root)
    roots = rec.to_json()
    assert len(roots) == 1
    assert roots[0]["children"][0]["name"] == "host.mine"


def test_close_is_idempotent_and_open_spans_export():
    rec = TraceRecorder()
    sid = rec.open("request")
    rec.close(sid, ok=True)
    t1 = rec.spans[sid]["t1"]
    rec.close(sid, ok=False)  # second close: no-op
    assert rec.spans[sid]["t1"] == t1 and rec.spans[sid]["args"] == {"ok": True}
    dangling = rec.open("stuck")
    ev = {e["args"].get("span_id"): e for e in rec.to_chrome()}
    assert ev[dangling]["args"]["open"] is True
    assert rec.spans[dangling]["t1"] is None  # export did not mutate it


def test_save_chrome_roundtrips(tmp_path):
    rec = TraceRecorder()
    with rec.span("request"):
        pass
    path = tmp_path / "trace.json"
    assert rec.save_chrome(str(path)) == 1
    events = json.loads(path.read_text())
    assert events[0]["name"] == "request" and events[0]["cat"] == "mining"


# --------------------------------------------------------------- emitter
def test_emitter_periodic_lines_and_final_snapshot():
    sink = io.StringIO()
    reg = Registry()
    reg.histogram("x_s").record(0.01)
    with StatsEmitter(reg.snapshot, sink, interval_s=0.01) as em:
        time.sleep(0.08)
    lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert em.stats["periodic"] >= 2 and em.stats["errors"] == 0
    assert len(lines) == em.stats["emits"]
    assert lines[-1]["reason"] == "final"
    for i, line in enumerate(lines):
        assert line["schema"] == SCHEMA_VERSION and line["seq"] == i
        assert line["stats"]["histograms"]["x_s"]["count"] == 1
        assert line["uptime_s"] >= 0


def test_emitter_swallows_chaos_drops_and_keeps_ticking():
    sink = io.StringIO()
    em = StatsEmitter(lambda: {"ok": 1}, sink, interval_s=0.01)
    inj = ChaosInjector().arm("telemetry.emit", times=2)
    with installed(inj):
        assert em.emit_once() is False
        assert em.emit_once() is False
        assert em.emit_once() is True  # schedule exhausted -> line lands
    assert em.stats["dropped"] == 2 and em.stats["emits"] == 1
    assert em.stats["errors"] == 0
    assert len(sink.getvalue().splitlines()) == 1


def test_emitter_counts_snapshot_and_sink_errors():
    def boom():
        raise RuntimeError("snapshot failed")

    em = StatsEmitter(boom, io.StringIO(), interval_s=0.01)
    assert em.emit_once() is False and em.stats["errors"] == 1

    class BadSink:
        def write(self, s):
            raise OSError("disk gone")

    em2 = StatsEmitter(lambda: {}, BadSink(), interval_s=0.01)
    assert em2.emit_once() is False and em2.stats["errors"] == 1
    em2.stop(final=False)


def test_emitter_file_sink_creates_parents(tmp_path):
    path = tmp_path / "deep" / "stats.jsonl"
    with StatsEmitter(lambda: {"n": 1}, str(path), interval_s=5.0):
        pass  # no periodic tick fits; stop() emits the final line
    lines = path.read_text().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["reason"] == "final"


def test_emitter_rejects_bad_interval():
    with pytest.raises(ValueError):
        StatsEmitter(lambda: {}, io.StringIO(), interval_s=0.0)


# ---------------------------------------------------------------- wiring
def test_engine_records_stage_and_prep_histograms():
    eng = MiningEngine()
    rows = random_db(np.random.default_rng(2), 100, 10, 6)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    eng.submit(rows, 10, spec)
    hs = eng.telemetry.snapshot()["histograms"]
    assert hs["engine.mine_s"]["count"] == 1
    assert hs["engine.prep_s"]["count"] == 1
    for stage in ("job1_flist", "job2_ppc_pack", "f2_scan"):
        assert hs[f"engine.stage.{stage}_s"]["count"] == 1
    eng.submit(rows, 10, spec)  # warm: served from the prep cache
    hs = eng.telemetry.snapshot()["histograms"]
    assert hs["engine.cache_hit_s"]["count"] >= 1
    assert hs["engine.mine_s"]["count"] == 2


def test_service_stats_report_populated_histograms():
    from repro.mining.service import MiningService

    rows = random_db(np.random.default_rng(1), 140, 10, 6)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    rec = TraceRecorder()
    with MiningService(batch_window_s=0.01) as svc, trace.attached(rec):
        futs = svc.sweep(rows, 10, spec, [0.3, 0.2])
        futs.append(svc.submit(rows, 10, spec.with_(algorithm="apriori")))
        svc.drain()
        for f in futs:
            f.result()
        snap = svc.stats()
    hists = snap["histograms"]
    for key in ("admission.queue_wait_s", "engine.prep_s", "engine.mine_s",
                "service.request_s", "scheduler.serve_s"):
        h = hists[key]
        assert h["count"] >= 1, key
        assert h["min_s"] <= h["p50_s"] <= h["p95_s"] <= h["p99_s"] <= h["max_s"]
    assert hists["service.request_s"]["count"] == 3
    assert snap["telemetry"]["schema"] == SCHEMA_VERSION
    # drained: gauges back to zero
    assert snap["telemetry"]["gauges"]["admission.queue_depth"] == 0
    assert snap["telemetry"]["gauges"]["admission.bytes_in_flight"] == 0
    json.dumps(snap, default=str)
    # every request produced a full span tree under the attached recorder
    roots = [r for r in rec.to_json() if r["name"] == "request"]
    assert len(roots) == 3
    for r in roots:
        names = {c["name"] for c in r["children"]}
        assert "admission.wait" in names and "resolve" in names


def test_stream_append_and_query_histograms():
    eng = MiningEngine()
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    rng = np.random.default_rng(3)
    for _ in range(2):
        eng.append(random_db(rng, 40, 10, 6), 10, spec=spec)
    eng.submit_stream(spec)
    hs = eng.telemetry.snapshot()["histograms"]
    assert hs["stream.default.append_s"]["count"] == 2
    assert hs["stream.default.query_s"]["count"] == 1


def test_distributed_mine_records_per_worker_wave_histograms():
    rng = np.random.default_rng(1)
    batches = [random_db(rng, n, 10, 6) for n in (25, 18, 31)]
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.15)
    from repro.mining.stream import StreamSpec

    eng = MiningEngine()
    dm = eng.distribute(name="t", n_items=10, workers=2, spec=spec,
                        stream_spec=StreamSpec(row_pad=16))
    try:
        for b in batches:
            dm.append(b)
        res = dm.mine(spec)
        assert any(len(s) >= 2 for s in res.itemsets)  # waves really ran
        hs = eng.telemetry.snapshot()["histograms"]
        worker_hists = [k for k in hs if k.startswith("dist.t.worker")]
        assert len(worker_hists) == 2  # one wave-RPC histogram per worker
        for k in worker_hists:
            assert k.endswith(".wave_rpc_s") and hs[k]["count"] >= 1
        assert hs["dist.t.append_s"]["count"] == len(batches)
        assert hs["dist.t.query_s"]["count"] == 1
    finally:
        dm.close()


# ------------------------------------------ prep stages, batching, hold
PREP_SPANS = ["prep.split", "prep.job1", "prep.job2", "prep.pack", "prep.f2"]


def test_prepare_records_stage_spans_that_sum_to_the_stage_times():
    eng = MiningEngine()
    rows = random_db(np.random.default_rng(4), 120, 10, 6)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    rec = TraceRecorder()
    with trace.attached(rec):
        res = eng.submit(rows, 10, spec)
    prep = sorted((s for s in rec.spans.values() if s["name"].startswith("prep.")),
                  key=lambda s: s["t0"])
    assert [s["name"] for s in prep] == PREP_SPANS
    span_s = sum(s["t1"] - s["t0"] for s in prep)
    stage_s = sum(res.stage_times_s[k] for k in ("job1_flist", "job2_ppc_pack", "f2_scan"))
    assert span_s == pytest.approx(stage_s, rel=0.05)


def test_service_batch_window_span_and_append_queue_time():
    from repro.mining.service import MiningService

    rng = np.random.default_rng(6)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    rec = TraceRecorder()
    with MiningService(batch_window_s=0.02) as svc, trace.attached(rec):
        out = svc.append(random_db(rng, 40, 10, 6), 10, spec=spec).result(timeout=120)
        svc.drain()
        batches = svc.stats["batches"]
    # an append's answer gains the queue time and no other serving telemetry
    assert out["queue_time_s"] >= 0
    assert "hold_s" not in out and "batch_size" not in out
    windows = [s for s in rec.spans.values() if s["name"] == "service.batch_window"]
    assert batches == 1 and len(windows) == 1
    (root,) = [sid for sid, s in rec.spans.items() if s["name"] == "request"]
    assert windows[0]["parent"] == root
    assert windows[0]["t1"] - windows[0]["t0"] >= 0.02 * 0.9


def test_sweep_results_carry_their_resolve_hold():
    from repro.mining.service import MiningService

    rows = random_db(np.random.default_rng(8), 160, 10, 6)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8)
    with MiningService(batch_window_s=0.2) as svc:
        results = [f.result(timeout=120)
                   for f in svc.sweep(rows, 10, spec, [0.4, 0.3, 0.2, 0.15])]
    assert {r.service_stats["batch_size"] for r in results} == {4}
    holds = [r.service_stats["hold_s"] for r in results]
    assert all(h >= 0 for h in holds)
    # every answer waits for the thresholds served after it
    assert holds[0] > holds[-1]
    # the engine's answer stamp is turned into the hold, not passed on
    assert not any("answered_at" in r.service_stats for r in results)


def test_stream_query_in_an_append_batch_carries_its_hold():
    from repro.mining.service import MiningService

    rng = np.random.default_rng(10)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
    with MiningService(batch_window_s=0.2) as svc:
        svc.append(random_db(rng, 40, 10, 6), 10, spec=spec).result(timeout=120)
        fa = svc.append(random_db(rng, 40, 10, 6), 10, spec=spec)
        fq = svc.submit_stream(spec)
        fa2 = svc.append(random_db(rng, 40, 10, 6), 10, spec=spec)
        query = fq.result(timeout=120)
        appended = [fa.result(timeout=120), fa2.result(timeout=120)]
    st = query.service_stats
    assert st["batch_size"] == 3 and st["hold_s"] > 0
    assert "answered_at" not in st
    assert all("hold_s" not in a and a["queue_time_s"] >= 0 for a in appended)


def test_wave_slots_count_the_padded_slots_of_every_wave():
    eng = MiningEngine()
    rng = np.random.default_rng(9)
    spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.05)
    res = eng.submit(random_db(rng, 120, 10, 6), 10, spec)
    for _ in range(2):
        eng.append(random_db(rng, 50, 10, 6), 10, spec=spec)
    query = eng.submit_stream(spec)
    for r in (res, query):
        st = r.stage_times_s
        assert any(len(s) >= 2 for s in r.itemsets)  # waves really ran
        assert st["wave_slots"] >= st["planned_candidates"] > 0
        assert st["wave_slots"] >= st["largest_wave"]
