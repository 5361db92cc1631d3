"""Cross-process PreparedDB persistence: the snapshot store.

Acceptance anchor (ISSUE 4): a fresh process pointed at a snapshot dir
serves a sweep with ``prepares == 0`` in engine stats, zero prep stage
counters on the miner, zeroed prep stage keys on every result, and
itemsets identical to a cold mine. Plus: corrupted/partial snapshots are
rejected (and healed), the store GC honors its byte budget, and shard-
count mismatches degrade to a rebuild instead of wrong answers.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.data.synth import random_db
from repro.mining import MineRequest, MineSpec, MiningEngine, SnapshotStore

SPEC = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3,
                nlist_width=16)
PREP_KEYS = ("job1_flist", "job2_ppc_pack", "f2_scan")


def _db(seed=0, n_tx=60, n_items=10):
    return random_db(np.random.default_rng(seed), n_tx, n_items, 6), n_items


def _counters(eng, spec=SPEC):
    return dict(eng.frontend("hprepost").miner_for(spec).stage_counters)


# ---------------------------------------------------------- warm-start parity
def test_fresh_engine_warm_starts_sweep_with_zero_prep_stages(tmp_path):
    rows, n_items = _db()
    cold = MiningEngine(snapshot_dir=str(tmp_path))
    ref = cold.sweep(rows, n_items, SPEC, [0.4, 0.3, 0.2])
    assert cold.snapshot_store.stats["stores"] == 1

    warm = MiningEngine(snapshot_dir=str(tmp_path))  # fresh "process"
    out = warm.sweep(rows, n_items, SPEC, [0.4, 0.3, 0.2])
    assert warm.stats["prepares"] == 0  # the acceptance criterion
    c = _counters(warm)
    assert c["job1"] == c["job2"] == c["pack"] == c["f2"] == 0
    assert warm.cache_info()["snapshot_hits"] == 1
    for a, b in zip(ref, out):
        assert b.itemsets == a.itemsets
        assert b.total_count == a.total_count
        assert b.peak_bytes == a.peak_bytes
        assert b.prep_shared  # nobody paid prep in this process
        assert b.service_stats["prep_source"] == "snapshot"
        for k in PREP_KEYS:  # zeroed prep stage keys
            assert b.stage_times_s[k] == 0.0


def test_adhoc_submit_warm_starts_and_loads_once(tmp_path):
    rows, n_items = _db(1)
    ref = MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, n_items, SPEC)

    warm = MiningEngine(snapshot_dir=str(tmp_path))
    r1 = warm.submit(rows, n_items, SPEC)
    r2 = warm.submit(rows, n_items, SPEC)
    assert r1.itemsets == ref.itemsets and r2.itemsets == ref.itemsets
    info = warm.cache_info()
    # disk is consulted once; the loaded entry then serves from the LRU
    assert info["snapshot_hits"] == 1 and info["hits"] == 1
    assert r1.service_stats["prep_source"] == "snapshot"
    assert r2.service_stats["prep_source"] == "cache"
    assert _counters(warm)["job1"] == 0


def test_tighter_threshold_served_from_snapshot_looser_rebuilds(tmp_path):
    rows, n_items = _db(2)
    MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, n_items, SPEC)

    warm = MiningEngine(snapshot_dir=str(tmp_path))
    tight = warm.submit(rows, n_items, SPEC.with_(min_sup=0.4))
    assert tight.service_stats["prep_source"] == "snapshot"
    # looser than the stored floor: unusable -> rebuild (and re-spill)
    loose = warm.submit(rows, n_items, SPEC.with_(min_sup=0.15))
    assert loose.service_stats["prep_source"] == "built"
    assert warm.cache_info()["snapshot_misses"] == 1
    assert _counters(warm)["job1"] == 1
    fresh = MiningEngine()
    assert loose.itemsets == fresh.submit(rows, n_items, SPEC.with_(min_sup=0.15)).itemsets
    # the re-spill replaced the entry: its looser floor serves a third process
    third = MiningEngine(snapshot_dir=str(tmp_path))
    assert third.submit(
        rows, n_items, SPEC.with_(min_sup=0.15)
    ).service_stats["prep_source"] == "snapshot"


def test_spill_policy_keeps_the_better_entry(tmp_path):
    rows, n_items = _db(3)
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    eng.submit(rows, n_items, SPEC)
    store = eng.snapshot_store
    assert store.stats["stores"] == 1
    # a tighter-floor rebuild in another "process" must not degrade the store
    other = MiningEngine(snapshot_store=store)
    other.clear_prep_cache()
    other.submit(rows, n_items, SPEC.with_(min_sup=0.4))  # snapshot hit, no spill
    assert store.stats["stores"] == 1
    # F1-only prep never replaces wave state on disk either, even at a
    # looser floor: the spill is refused, the full entry keeps serving
    other2 = MiningEngine(snapshot_store=SnapshotStore(str(tmp_path)))
    res = other2.submit(rows, n_items, SPEC.with_(max_k=1, min_sup=0.2))
    assert res.itemsets  # built F1-only (floor 0.2 < stored 0.3 -> miss)
    assert other2.snapshot_store.stats["store_skips"] == 1
    (entry,) = other2.snapshot_store.entries()
    meta = other2.snapshot_store.peek_meta(os.path.basename(entry))
    assert meta["f1_only"] is False  # wave state survived the F1-only spill


# ----------------------------------------------------- corruption / partials
def _entry_paths(tmp_path):
    store = SnapshotStore(str(tmp_path))
    return store.entries()


def test_corrupted_array_is_rejected_deleted_and_healed(tmp_path):
    rows, n_items = _db(4)
    ref = MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, n_items, SPEC)
    (entry,) = _entry_paths(tmp_path)
    target = os.path.join(entry, "packed.npy")
    raw = bytearray(open(target, "rb").read())
    raw[-1] ^= 0xFF  # flip one payload byte: digest must catch it
    open(target, "wb").write(bytes(raw))

    warm = MiningEngine(snapshot_dir=str(tmp_path))
    res = warm.submit(rows, n_items, SPEC)  # must rebuild, not crash/misread
    assert res.itemsets == ref.itemsets
    assert res.service_stats["prep_source"] == "built"
    info = warm.cache_info()["snapshot_store"]
    assert info["corrupt"] == 1
    assert warm.cache_info()["snapshot_misses"] == 1
    # the rejected entry was deleted and the rebuild re-spilled a good one
    assert info["stores"] == 1 and info["entries"] == 1
    third = MiningEngine(snapshot_dir=str(tmp_path))
    assert third.submit(
        rows, n_items, SPEC
    ).service_stats["prep_source"] == "snapshot"  # healed


def test_partial_snapshot_missing_manifest_is_a_miss(tmp_path):
    rows, n_items = _db(5)
    MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, n_items, SPEC)
    (entry,) = _entry_paths(tmp_path)
    os.remove(os.path.join(entry, "manifest.json"))
    warm = MiningEngine(snapshot_dir=str(tmp_path))
    res = warm.submit(rows, n_items, SPEC)
    assert res.service_stats["prep_source"] == "built"
    assert warm.cache_info()["snapshot_store"]["corrupt"] == 1


def test_tampered_meta_shape_is_rejected_by_from_host(tmp_path):
    # digests pass (we re-sign), but the payload no longer matches itself:
    # from_host's structural validation is the last line of defense
    rows, n_items = _db(6)
    MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, n_items, SPEC)
    (entry,) = _entry_paths(tmp_path)
    mpath = os.path.join(entry, "manifest.json")
    manifest = json.load(open(mpath))
    manifest["meta"]["width"] = manifest["meta"]["width"] * 2
    json.dump(manifest, open(mpath, "w"))
    warm = MiningEngine(snapshot_dir=str(tmp_path))
    res = warm.submit(rows, n_items, SPEC)
    assert res.service_stats["prep_source"] == "built"
    assert warm.cache_info()["snapshot_misses"] == 1


# ------------------------------------------------------------------ store GC
def test_gc_honors_byte_budget_and_evicts_oldest(tmp_path):
    rows_a, n_items = _db(7)
    rows_b, _ = _db(8)
    probe = MiningEngine(snapshot_dir=str(tmp_path / "probe"))
    probe.submit(rows_a, n_items, SPEC)
    one = probe.snapshot_store.bytes_in_use()
    assert one > 0

    store = SnapshotStore(str(tmp_path / "real"), byte_budget=int(one * 1.5))
    eng = MiningEngine(snapshot_store=store)
    eng.submit(rows_a, n_items, SPEC)
    os.utime(store.entries()[0], (1, 1))  # age entry a well below entry b
    eng.submit(rows_b, n_items, SPEC)
    info = store.info()
    assert info["evictions"] == 1 and info["entries"] == 1
    assert info["bytes_in_use"] <= info["byte_budget"]
    # the survivor is rows_b's entry: a fresh engine warm-starts on b, not a
    fresh = MiningEngine(snapshot_store=store)
    assert fresh.submit(rows_b, n_items, SPEC).service_stats["prep_source"] == "snapshot"
    fresh2 = MiningEngine(snapshot_store=store)
    assert fresh2.submit(rows_a, n_items, SPEC).service_stats["prep_source"] == "built"


def test_zero_budget_store_keeps_nothing(tmp_path):
    rows, n_items = _db(9)
    store = SnapshotStore(str(tmp_path), byte_budget=0)
    eng = MiningEngine(snapshot_store=store)
    eng.submit(rows, n_items, SPEC)
    assert store.info()["entries"] == 0 and store.stats["evictions"] == 1


def test_spill_failure_is_best_effort(tmp_path, monkeypatch):
    # a full/readonly disk must cost the snapshot, never the answer
    rows, n_items = _db(14)
    store = SnapshotStore(str(tmp_path))

    def broken_put(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(store, "put", broken_put)
    eng = MiningEngine(snapshot_store=store)
    res = eng.submit(rows, n_items, SPEC)
    assert res.itemsets and res.service_stats["prep_source"] == "built"
    assert eng.cache_info()["snapshot_spill_failures"] == 1
    # the LRU entry made it in regardless: the next submit is prep-free
    assert eng.submit(rows, n_items, SPEC).service_stats["prep_source"] == "cache"


def test_checkpoint_keep_zero_retains_everything(tmp_path):
    # the GC refactor must preserve the old slicing semantics (keep=0
    # deleted nothing) for the checkpoint writer it was factored from
    from repro.checkpoint.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=0)
    for step in (1, 2, 3):
        mgr.save(step, {"w": np.ones(2)})
    assert mgr.list_steps() == [1, 2, 3]


# --------------------------------------------------------- shard-count gates
def test_from_host_rejects_shard_count_mismatch():
    from repro.core.hprepost import HPrepostConfig, HPrepostMiner, PreparedDB
    from repro.mining.miners import default_mesh

    rows, n_items = _db(10)
    miner = HPrepostMiner(default_mesh(), config=HPrepostConfig(candidate_unit=8))
    payload = miner.prepare(rows, n_items, 12).to_host()
    payload["n_shards"] = 2
    with pytest.raises(ValueError, match="shard"):
        PreparedDB.from_host(payload, miner)


def test_cross_shard_count_warm_start_where_mesh_allows(tmp_path):
    # snapshots restore onto any mesh with the SAME data-shard count (the
    # model axis is free); a different D degrades to a clean rebuild. Needs
    # fake devices -> subprocess, like benchmarks/bench_scaling.
    script = textwrap.dedent(
        """
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import numpy as np
        from repro.launch.mesh import make_mesh
        from repro.data.synth import random_db
        from repro.mining import MineSpec, MiningEngine

        snap = sys.argv[1]
        rows = random_db(np.random.default_rng(0), 60, 10, 6)
        spec = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8,
                        min_sup=0.3, nlist_width=16)

        writer = MiningEngine(make_mesh((2, 1), ("data", "model")), snapshot_dir=snap)
        ref = writer.submit(rows, 10, spec)

        # same D=2, different model-axis split: the mesh allows it
        same_d = MiningEngine(make_mesh((2, 1), ("data", "model")), snapshot_dir=snap)
        warm = same_d.submit(rows, 10, spec)
        assert warm.service_stats["prep_source"] == "snapshot", warm.service_stats
        assert same_d.stats["prepares"] == 0
        assert warm.itemsets == ref.itemsets

        # D=1 mesh: per-shard PPC state cannot re-shard -> rebuild, same answer
        other_d = MiningEngine(make_mesh((1, 2), ("data", "model")), snapshot_dir=snap)
        cold = other_d.submit(rows, 10, spec)
        assert cold.service_stats["prep_source"] == "built", cold.service_stats
        assert other_d.cache_info()["snapshot_misses"] == 1
        assert cold.itemsets == ref.itemsets
        print("OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
