"""Smoke run of the served HPrepost mining path on a TPU.

    python chip_smoke.py            # one chip: serve and stream phases
    python chip_smoke.py --chips 4  # four chips: the MapReduce-partitioned
                                    # path on a 4x1 and a 2x2 mesh

Drives the normal entry points once at the FIMI datasets' published sizes
(kosarak: 990,002 rows; mushroom: 8,124 rows; seeded surrogates from
``repro.data.synth``) and checks every answer for exact equality with host
FP-growth on the same rows.

One chip:
  serve   a ``MiningService`` on the 1x1 mesh answers kosarak at
          min_sup=0.01, the sweep 0.02/0.015/0.01 on the same database, and
          mushroom at min_sup=0.13 (max_k=6 throughout), each twice: cold
          (compiles and prep included) and warm. No group may degrade to
          per-request retries, every kernel plan must be ``pallas-tpu``,
          and the single-shard waves must run the early-stop kernel with a
          nonzero threshold.
  stream  mushroom appended in 4 batches into a 3-batch sliding window,
          then one query, checked against FP-growth over exactly the
          window's rows (a kosarak batch's ~26,000 distinct items exceed
          the segment prep's item cap).
Four chips: kosarak and mushroom at the thresholds above on each mesh; each
device must hold a distinct shard of the rows.

Runs in one process and starts no other. Without a TPU it exits non-zero
before any phase runs. The last line of its output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any mismatch or error exits non-zero without it. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

MAX_K = 6
KOSARAK_SUP = 0.01
SWEEP = (0.02, 0.015, 0.01)
# mushroom's largest wave at min_sup=0.12 is 262,144 candidate slots of
# 2,048-slot N-lists, which does not fit one v5e's 16 GB of HBM (waves are
# not chunked); at 0.13 it is 65,536 slots
MUSHROOM_SUP = 0.13
STREAM_BATCHES = 4
STREAM_WINDOW = 3
FULL = {"kosarak": 1.0, "mushroom": 1.0}


def log(msg: str) -> None:
    print(msg, flush=True)


def min_count(min_sup: float, n_rows: int) -> int:
    from repro.mining import MineSpec

    return MineSpec(min_sup=min_sup).resolve(n_rows)


class Reference:
    """Host FP-growth answers, one mine per (rows, loosest threshold): a
    tighter threshold's answer is the looser answer filtered by support."""

    def __init__(self):
        self._answers: dict = {}

    def get(self, key: str, rows, n_items: int, count: int, floor: int) -> dict:
        from repro.core.fpgrowth import mine_fpgrowth

        if key not in self._answers:
            t0 = time.perf_counter()
            ans, _ = mine_fpgrowth(rows, n_items, floor, max_k=MAX_K)
            self._answers[key] = ans
            log(f"  reference {key}: fpgrowth min_count={floor} -> {len(ans)} "
                f"itemsets in {time.perf_counter() - t0:.3f} s (host)")
        return {k: v for k, v in self._answers[key].items() if v >= count}


def check(tag: str, got: dict, want: dict) -> None:
    if got != want:
        missing = len(want.keys() - got.keys())
        extra = len(got.keys() - want.keys())
        wrong = sum(1 for k in want.keys() & got.keys() if want[k] != got[k])
        raise AssertionError(
            f"{tag}: answer differs from fpgrowth ({len(got)} vs {len(want)} "
            f"itemsets; {missing} missing, {extra} extra, {wrong} wrong supports)"
        )


def timed(futures) -> list:
    """-> [(result, seconds from now until that future resolved)]."""
    t0 = time.perf_counter()
    done = [0.0] * len(futures)
    for i, f in enumerate(futures):
        f.add_done_callback(lambda _f, i=i: done.__setitem__(i, time.perf_counter()))
    results = [f.result() for f in futures]
    return [(r, done[i] - t0) for i, r in enumerate(results)]


def wave_info(res) -> str:
    """The largest wave (candidate slots on the device) and the analytic
    peak of the mining structures, from the result's planning counters."""
    return (f"largest wave {int(res.stage_times_s.get('largest_wave', 0))} "
            f"candidate slots, analytic peak {res.peak_bytes} B")


def resolved_plans(engine) -> list:
    """Every KernelPlan the engine's HPrepost miners resolved."""
    fe = engine.frontend("hprepost")
    return [p for m in fe._miners.values() for p in m._plan_cache.values()]


def check_plans(engine, backend: str) -> None:
    plans = resolved_plans(engine)
    for p in sorted(set(plans), key=repr):
        log(f"  plan: {p}")
    if not plans or any(p.backend != backend for p in plans):
        raise AssertionError(f"expected only {backend} plans, got {plans}")


def kernel_phase(*, scale=FULL, interpret=False) -> None:
    """Each Pallas kernel on the chip against an exact host or jnp reference
    at a real shape. Interpret mode cannot show what differs only on the TPU
    (output blocks kept in VMEM, MXU precision), so a fault there is named
    here before it surfaces as a wrong itemset."""
    import numpy as np

    from repro.core import encoding as enc
    from repro.core.ppc import build_ppc
    from repro.data import synth
    from repro.kernels.cooccur.kernel import cooccur_pallas
    from repro.kernels.histogram.kernel import histogram_pallas
    from repro.kernels.nlist_intersect.kernel import (
        nlist_intersect_pallas, nlist_intersect_pallas_es)
    from repro.kernels.nlist_intersect.ref import (
        nlist_intersect_fused_ref, nlist_intersect_masked_ref)

    def same(tag, got, want):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"kernel {tag} differs from its reference")
        log(f"  {tag} == reference")

    kos, kos_n = synth.load("kosarak", scale=scale["kosarak"], seed=0)
    ones = np.ones(len(kos), np.int32)
    same(f"histogram {kos.shape} over {kos_n} bins",
         histogram_pallas(kos, ones, n_bins=kos_n, interpret=interpret),
         np.bincount(kos[kos >= 0], minlength=kos_n))
    mush, mush_n = synth.load("mushroom", scale=scale["mushroom"], seed=0)
    onehot = np.zeros((len(mush), mush_n + 1), np.int64)
    np.put_along_axis(onehot, np.where(mush >= 0, mush, mush_n), 1, axis=1)
    onehot = onehot[:, :mush_n]
    same(f"cooccur {mush.shape} over {mush_n} items",
         cooccur_pallas(mush, np.ones(len(mush), np.int32), n_items=mush_n,
                        interpret=interpret),
         onehot.T @ onehot)
    # N-list pairs of mushroom's own PPC tree: real PP codes, counts > 256
    fl = enc.build_flist(enc.item_support(mush, mush_n), 1)
    urows, w = enc.dedup_rows(enc.rank_encode(mush, fl))
    nls = build_ppc(urows, w).nlists(fl.k)
    W = max(8, max(len(n) for n in nls))
    rng = np.random.default_rng(0)
    pairs = [sorted(rng.choice(fl.k, size=2, replace=False)) for _ in range(64)]
    shape = (2, len(pairs), W)  # padding: pre = INT32_MAX, post = -1, cnt = 0
    a_pre, y_pre = np.full(shape, np.iinfo(np.int32).max, np.int32)
    a_post, y_post = np.full(shape, -1, np.int32)
    a_cnt, y_cnt = np.zeros(shape, np.int32)
    for b, (qa, qy) in enumerate(pairs):
        A, Y = nls[qa], nls[qy]
        a_pre[b, :len(A)], a_post[b, :len(A)], a_cnt[b, :len(A)] = A.T
        y_pre[b, :len(Y)], y_post[b, :len(Y)], y_cnt[b, :len(Y)] = Y.T
    want = nlist_intersect_fused_ref(a_pre, a_post, y_pre, y_post, y_cnt)
    got = nlist_intersect_pallas(a_pre, a_post, y_pre, y_post, y_cnt,
                                 interpret=interpret)
    same(f"nlist_intersect exact ({len(pairs)}, {W})", got[0], want[0])
    same(f"nlist_intersect exact supports ({len(pairs)}, {W})", got[1], want[1])
    stop = int(np.median(np.asarray(want[1])))
    got = nlist_intersect_pallas_es(a_pre, a_post, a_cnt, y_pre, y_post, y_cnt,
                                    stop, interpret=interpret)
    want = nlist_intersect_masked_ref(a_pre, a_post, a_cnt, y_pre, y_post, y_cnt, stop)
    same(f"nlist_intersect early stop at {stop} ({len(pairs)}, {W})", got[0], want[0])
    same("nlist_intersect early stop supports", got[1], want[1])


def serve_phase(*, scale=FULL, backend="auto", plan_backend="pallas-tpu",
                need_stop=True) -> None:
    """One MiningService on the 1x1 mesh: kosarak, the kosarak sweep and
    mushroom, each cold then warm, every answer against FP-growth. Every
    plan must resolve to ``plan_backend``; with ``need_stop`` some wave
    must have run the in-kernel early stop."""
    from repro.data import synth
    from repro.launch.mesh import make_mesh
    from repro.mining import MineSpec
    from repro.mining.service import MiningService

    kos, kos_n = synth.load("kosarak", scale=scale["kosarak"], seed=0)
    mush, mush_n = synth.load("mushroom", scale=scale["mushroom"], seed=0)
    log(f"serve: kosarak {kos.shape}, mushroom {mush.shape}")
    spec = MineSpec(max_k=MAX_K, backend=backend)
    ref = Reference()
    svc = MiningService(mesh=make_mesh((1, 1), ("data", "model")))
    try:
        requests = [
            ("kosarak", kos, kos_n, (KOSARAK_SUP,), min(SWEEP)),
            ("kosarak sweep", kos, kos_n, SWEEP, min(SWEEP)),
            ("mushroom", mush, mush_n, (MUSHROOM_SUP,), MUSHROOM_SUP),
        ]
        for name, rows, n_items, sups, floor in requests:
            for run in ("cold", "warm"):
                outs = timed(svc.sweep(rows, n_items, spec, sups))
                for sup, (res, secs) in zip(sups, outs):
                    want = ref.get(name.split()[0], rows, n_items,
                                   min_count(sup, len(rows)), min_count(floor, len(rows)))
                    check(f"{name} min_sup={sup} ({run})", res.itemsets, want)
                    log(f"  {name} min_sup={sup} {run}: {secs:.3f} s, "
                        f"{len(res.itemsets)} itemsets == fpgrowth, "
                        f"prep={res.service_stats.get('prep_source')}, "
                        f"{wave_info(res)}")
        degraded = svc.scheduler.stats["degraded_groups"]
        log(f"  degraded_groups={degraded}")
        if degraded:
            raise AssertionError(f"{degraded} group(s) degraded to per-request retries")
        check_plans(svc.engine, plan_backend)
        fe = svc.engine.frontend("hprepost")
        stop_waves = sum(m.stage_counters["stop_waves"] for m in fe._miners.values())
        waves = sum(m.stage_counters["waves"] for m in fe._miners.values())
        log(f"  waves={waves}, early-stop waves with a nonzero threshold={stop_waves}")
        if need_stop and not stop_waves:
            raise AssertionError("no single-shard wave ran the early-stop kernel")
    finally:
        svc.close()


def stream_phase(*, scale=FULL, backend="auto", plan_backend="pallas-tpu") -> None:
    """mushroom appended in batches into a sliding window, then one query
    against FP-growth over exactly the window's rows. Each stream segment
    is prepared over every item its batch holds; a kosarak batch holds
    ~26,000 distinct items, past ``HPrepostConfig.max_f1``, so kosarak
    cannot be streamed until the large-universe work lands."""
    import numpy as np

    from repro.data import synth
    from repro.launch.mesh import make_mesh
    from repro.mining import MineSpec
    from repro.mining.service import MiningService
    from repro.mining.stream import StreamSpec

    rows, n_items = synth.load("mushroom", scale=scale["mushroom"], seed=0)
    batches = np.array_split(rows, STREAM_BATCHES)
    spec = MineSpec(max_k=MAX_K, backend=backend, min_sup=MUSHROOM_SUP)
    log(f"stream: mushroom in {STREAM_BATCHES} batches of ~{len(batches[0])} rows, "
        f"window {STREAM_WINDOW} batches")
    svc = MiningService(mesh=make_mesh((1, 1), ("data", "model")))
    try:
        sspec = StreamSpec(window_batches=STREAM_WINDOW)
        for i, b in enumerate(batches):
            (_, secs), = timed([svc.append(b, n_items, stream="mushroom", spec=spec,
                                           stream_spec=sspec)])
            log(f"  append {i}: {secs:.3f} s")
        (res, secs), = timed([svc.submit_stream(spec, stream="mushroom")])
        window = np.concatenate(batches[-STREAM_WINDOW:])
        count = min_count(MUSHROOM_SUP, len(window))
        if res.n_rows != len(window) or res.min_count != count:
            raise AssertionError(f"stream query saw {res.n_rows} rows at min_count="
                                 f"{res.min_count}, window has {len(window)} at {count}")
        want = Reference().get("mushroom window", window, n_items, count, count)
        check("stream query", res.itemsets, want)
        log(f"  query: {secs:.3f} s, {len(res.itemsets)} itemsets == fpgrowth over "
            f"the window's {len(window)} rows, {wave_info(res)}")
        check_plans(svc.engine, plan_backend)
    finally:
        svc.close()


def check_shards(prepared, mesh) -> int:
    """Each data shard of ``prepared`` holds a distinct block of the rows:
    devices on one ``data`` index hold identical N-lists, and the per-shard
    item supports add up to the global ones exactly once. -> shard count."""
    import numpy as np

    by_index: dict = {}
    for s in prepared.packed.addressable_shards:
        d = s.index[0].start or 0
        counts = np.asarray(s.data)[0, :, :, 2].sum(axis=1)
        if d in by_index and not np.array_equal(by_index[d], counts):
            raise AssertionError(f"replicas of data shard {d} disagree")
        by_index[d] = counts
    D = mesh.shape["data"]
    if sorted(by_index) != list(range(D)):
        raise AssertionError(f"data shards {sorted(by_index)} on a mesh with D={D}")
    total = np.sum(list(by_index.values()), axis=0)
    if not np.array_equal(total, np.asarray(prepared.fl.supports)):
        raise AssertionError("per-shard supports do not add up to the global ones")
    if any(not c.sum() for c in by_index.values()):
        raise AssertionError("a data shard holds no rows")
    return D


def partitioned_phase(*, scale=FULL, backend="auto", shapes=((4, 1), (2, 2))) -> None:
    """kosarak and mushroom on each multi-device mesh through the HPrepost
    front end's two-phase path (prepare, then mine the prepared database)."""
    import jax

    from repro.data import synth
    from repro.launch.mesh import make_mesh
    from repro.mining import MineSpec, MiningEngine

    ref = Reference()
    data = {name: synth.load(name, scale=scale[name], seed=0)
            for name in ("kosarak", "mushroom")}
    sups = {"kosarak": KOSARAK_SUP, "mushroom": MUSHROOM_SUP}
    for shape in shapes:
        mesh = make_mesh(shape, ("data", "model"))
        engine = MiningEngine(mesh)
        fe = engine.frontend("hprepost")
        for name, (rows, n_items) in data.items():
            spec = MineSpec(max_k=MAX_K, backend=backend, min_sup=sups[name])
            count = spec.resolve(len(rows))
            t0 = time.perf_counter()
            miner, prepared = fe.prepare(rows, n_items, count, spec)
            res = fe.mine_prepared(miner, prepared, spec)
            secs = time.perf_counter() - t0
            D = check_shards(prepared, mesh)
            want = ref.get(name, rows, n_items, count, count)
            tag = f"{shape[0]}x{shape[1]} {name} min_sup={sups[name]}"
            check(tag, res.itemsets, want)
            log(f"  {tag}: {secs:.3f} s (cold), {len(res.itemsets)} itemsets == "
                f"fpgrowth, {D} distinct data shards over {len(jax.devices())} "
                f"devices, {wave_info(res)}")
        check_plans(engine, "pallas-tpu" if backend == "auto" else backend)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> str:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    return ", ".join("not reported" if p is None else str(p) for p in peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and stream phases; 4: the partitioned path only")
    args = ap.parse_args(argv)
    dev = device_info()
    log(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    if dev["platform"] != "tpu":
        log("chip_smoke: no TPU found; this script runs only on the chip")
        return 1
    if dev["count"] != args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"found {dev['count']}")
        return 1
    from repro.launch.compile_cache import use_compile_cache
    from repro.mining.tune import resolve_backend

    log(f"compile cache: {use_compile_cache()}")
    if resolve_backend("auto") != "pallas-tpu":
        raise AssertionError(f"auto resolves to {resolve_backend('auto')}")
    t0 = time.perf_counter()
    if args.chips == 4:
        partitioned_phase()
    else:
        kernel_phase()
        serve_phase()
        log(f"peak_bytes_in_use after serve: {peak_bytes()}")
        stream_phase()
    log(f"total: {time.perf_counter() - t0:.3f} s")
    log(f"peak_bytes_in_use per device: {peak_bytes()}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
