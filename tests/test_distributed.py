"""Distributed mining (repro.mining.distributed): coordinator/worker
placement, the RPC layer, and snapshot-based failover.

Anchors, per the PR acceptance criteria:
  - parity: a >= 2-worker distributed mine answers bit-identically to the
    single-process ``StreamingMiner`` on the same appended batches (and
    to the brute-force oracle), across min_sup thresholds, and through
    the ``MiningService`` Future path;
  - chaos: a worker hard-killed between waves, mid-wave (no reply), or
    during an append still yields the exact answer, with the dead
    worker's segments re-placed from the shared snapshot store with ZERO
    prep recompute on the survivors (snapshot-only recovery);
  - heartbeats: with a monitor enabled, a dead worker is detected and
    failed over without any query traffic.

Worker processes are real (multiprocessing spawn + loopback TCP), so the
parity tests share one module-scoped cluster; chaos tests get fresh ones.
"""
import time

import numpy as np
import pytest

from repro.core.oracle import mine_bruteforce
from repro.data.synth import random_db
from repro.mining import MineSpec, MiningEngine
from repro.mining.distributed import NoLiveWorkers, choose_worker, replan
from repro.mining.service import MiningService
from repro.mining.stream import StreamSpec

SPEC = MineSpec(algorithm="hprepost", max_k=4, candidate_unit=8, min_sup=0.3)
SSPEC = StreamSpec(row_pad=16)


def _batches(seed=0, sizes=(30, 14, 22), n_items=10, max_len=6):
    rng = np.random.default_rng(seed)
    return [random_db(rng, n, n_items, max_len) for n in sizes], n_items


def _single_process(batches, n_items, spec):
    eng = MiningEngine()
    for b in batches:
        eng.append(b, n_items, spec=SPEC, stream_spec=SSPEC)
    return eng.submit_stream(spec)


# ------------------------------------------------------------- placement
def test_choose_worker_picks_least_loaded_deterministically():
    assert choose_worker({0: 100, 1: 40, 2: 70}) == 1
    # ties break on worker id, never dict order
    assert choose_worker({2: 50, 0: 50, 1: 80}) == 0
    assert choose_worker({3: 0}) == 3


def test_replan_best_fit_decreasing_balances_bytes():
    loads = {1: 100, 2: 300}
    plan = replan([(10, 500), (11, 200), (12, 50)], loads)
    # biggest orphan lands on the lightest survivor, then re-balance
    assert plan == {10: 1, 11: 2, 12: 2}
    # loads mutated in place to reflect the plan
    assert loads == {1: 600, 2: 550}
    assert replan([], {5: 0}) == {}


# -------------------------------------------------------------- protocol
def test_protocol_roundtrip_with_arrays():
    import socket

    from repro.mining.distributed.protocol import (
        ConnectionClosed, recv_msg, send_msg)

    a, b = socket.socketpair()
    try:
        msg = {
            "op": "wave", "seq": 7,
            "parent_arr": np.arange(1000, dtype=np.int32),
            "sups": np.array([1, 2, 3], np.int64),
        }
        send_msg(a, msg)
        got = recv_msg(b)
        assert got["op"] == "wave" and got["seq"] == 7
        np.testing.assert_array_equal(got["parent_arr"], msg["parent_arr"])
        np.testing.assert_array_equal(got["sups"], msg["sups"])
        assert got["sups"].dtype == np.int64
        a.close()
        with pytest.raises(ConnectionClosed):
            recv_msg(b)  # clean EOF is a typed error, not a short read
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------- parity
@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    batches, n_items = _batches(1, sizes=(25, 18, 31, 12))
    snap = tmp_path_factory.mktemp("dist-snap")
    eng = MiningEngine(snapshot_dir=str(snap))
    dm = eng.distribute(
        name="t", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC
    )
    for b in batches:
        dm.append(b)
    yield eng, dm, batches, n_items
    dm.close()


@pytest.mark.parametrize("min_sup", [0.5, 0.3, 0.15])
def test_distributed_matches_single_process_and_oracle(cluster, min_sup):
    _, dm, batches, n_items = cluster
    spec = SPEC.with_(min_sup=min_sup)
    res = dm.mine(spec)
    ref = _single_process(batches, n_items, spec)
    allrows = np.concatenate(batches)
    assert res.n_rows == len(allrows)
    assert res.itemsets == ref.itemsets
    assert res.itemsets == mine_bruteforce(allrows, n_items, res.min_count,
                                           max_k=SPEC.max_k)
    assert res.service_stats["prep_source"] == "distributed"
    assert res.service_stats["workers"] == 2


def test_segments_spread_over_both_workers(cluster):
    _, dm, _, _ = cluster
    owners = {m.worker for m in dm._segments.values()}
    assert owners == {0, 1}  # byte-balanced placement used the whole pool


def test_workers_report_cpu_platform(cluster):
    """Workers run on the CPU (the coordinator's process may hold the
    accelerator) and say so in their hello and in their stats."""
    _, dm, _, _ = cluster
    assert {w.platform for w in dm._workers.values()} == {"cpu"}
    assert {s["platform"] for s in dm.worker_stats().values()} == {"cpu"}


def test_distributed_through_service_future_path(cluster):
    eng, dm, batches, n_items = cluster
    svc = MiningService(engine=eng)
    try:
        spec = SPEC.with_(min_sup=0.25)
        fut_res = svc.submit_stream(spec, stream="t")
        fut_append = svc.append(
            random_db(np.random.default_rng(7), 9, n_items, 6), stream="t"
        )
        assert fut_res.result(120).itemsets == _single_process(
            batches, n_items, spec).itemsets
        assert fut_append.result(120)["total_rows"] == dm.db.n_rows
        # the appended batch is part of the database for later queries
        res2 = svc.submit_stream(spec, stream="t").result(120)
        assert res2.n_rows == dm.db.n_rows
    finally:
        svc.close()


def test_mixed_device_config_query_rejected(cluster):
    _, dm, _, _ = cluster
    with pytest.raises(ValueError, match="device config"):
        dm.mine(SPEC.with_(candidate_unit=16))
    with pytest.raises(ValueError, match="hprepost"):
        dm.mine(SPEC.with_(algorithm="apriori"))


# ----------------------------------------------------------------- chaos
def _survivor_prepares(stats_by_wid, wids):
    return sum(stats_by_wid[w]["stats"]["seg_prepares"] for w in wids)


@pytest.mark.parametrize(
    "fault_op,after,when",
    [
        ("wave", 0, "after_reply"),  # dies between waves, reply flushed
        ("wave", 0, "before"),       # dies mid-wave, reply never sent
        ("prep", 0, "before"),       # dies during an append's map step
    ],
    ids=["between-waves", "mid-wave", "during-append"],
)
def test_chaos_worker_death_recovers_from_snapshots(tmp_path, fault_op, after, when):
    """Kill a worker at each dangerous point; the answer must stay exact
    and every re-placed segment must warm-restore from the shared
    snapshot store — failover recomputes nothing."""
    batches, n_items = _batches(3, sizes=(30, 14, 22))
    spec = SPEC.with_(min_sup=0.08)  # dense enough for 3-itemsets (2 waves)
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="chaos", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC
    )
    try:
        for b in batches:
            dm.append(b)
        ref = _single_process(batches, n_items, spec)
        assert any(len(s) >= 3 for s in ref.itemsets)  # multi-wave query
        assert dm.mine(spec).itemsets == ref.itemsets

        if fault_op == "prep":
            # the next append's map step must land on the faulted worker:
            # placement is deterministic (least loaded bytes, then wid)
            victim = choose_worker(dm._loads())
        else:
            victim = min(m.worker for m in dm._segments.values())
        pre = dm.worker_stats()
        dm.inject_fault(victim, fault_op, after=after, when=when)
        if fault_op == "prep":
            extra = random_db(np.random.default_rng(9), 18, n_items, 6)
            dm.append(extra)
            batches = batches + [extra]
            ref = _single_process(batches, n_items, spec)
        res = dm.mine(spec)
        assert res.itemsets == ref.itemsets  # bit-identical after failover

        survivors = {w.wid for w in dm._live()}
        assert victim not in survivors and len(survivors) == 1
        assert dm.stats["workers_lost"] == 1
        assert dm.stats["failovers"] >= 1
        # snapshot-only recovery: re-placed segments restored, not rebuilt
        assert dm.stats["reassigned_segments"] >= 1
        assert dm.stats["reassign_rebuilds"] == 0
        post = dm.worker_stats()
        # the survivors ran prep (full N-list build) only for a batch the
        # store had never seen: the in-flight append of the 'prep' case
        expected_new_preps = 1 if fault_op == "prep" else 0
        assert (_survivor_prepares(post, survivors)
                - _survivor_prepares(pre, survivors)) == expected_new_preps

        # the database stays serviceable: append + re-query on survivors
        extra2 = random_db(np.random.default_rng(11), 7, n_items, 6)
        dm.append(extra2)
        ref2 = _single_process(batches + [extra2], n_items, spec)
        assert dm.mine(spec).itemsets == ref2.itemsets
    finally:
        dm.close()


def test_all_workers_dead_raises_no_live_workers(tmp_path):
    batches, n_items = _batches(5, sizes=(20,))
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="dead", n_items=n_items, workers=1, spec=SPEC, stream_spec=SSPEC
    )
    try:
        dm.append(batches[0])
        dm.kill_worker(0)
        with pytest.raises(NoLiveWorkers):
            dm.mine(SPEC)
        with pytest.raises(NoLiveWorkers):
            dm.append(batches[0])
    finally:
        dm.close()


def test_heartbeat_detects_death_without_query_traffic(tmp_path):
    """With the monitor on, a hard-killed worker is retired and its
    segments re-placed by the heartbeat alone — the next query pays no
    mid-flight retry."""
    batches, n_items = _batches(6, sizes=(24, 17))
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="hb", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC,
        heartbeat_s=0.2,
    )
    try:
        for b in batches:
            dm.append(b)
        victim = min(w.wid for w in dm._live())
        dm.kill_worker(victim)

        # the failovers counter bumps at the *start* of the re-place loop
        # (the monitor holds _op_lock throughout), so wait for the whole
        # postcondition — detected AND every segment off the victim —
        # not just the counter, or a slow box observes mid-failover state
        def settled():
            with dm._op_lock:
                return dm.stats["failovers"] >= 1 and all(
                    m.worker != victim for m in dm._segments.values()
                )

        deadline = time.monotonic() + 30
        while not settled() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert settled()  # detected + re-placed with zero queries issued
        assert dm.stats["workers_lost"] == 1
        assert dm.stats["reassign_rebuilds"] == 0

        spec = SPEC.with_(min_sup=0.2)
        res = dm.mine(spec)
        assert dm.stats["query_retries"] == 0  # failover happened off-path
        assert res.itemsets == _single_process(batches, n_items, spec).itemsets
    finally:
        dm.close()


# ----------------------------------------------- transport hardening (PR 8)
def test_channel_sockets_are_hardened():
    import socket

    from repro.mining.distributed.transport import Listener, dial

    lst = Listener()
    try:
        peer = dial(lst.address)
        chan = lst.accept(5)
        for c in (peer, chan):
            s = c.sock
            assert s.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY) != 0
            assert s.getsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE) != 0
        peer.close()
        chan.close()
    finally:
        lst.close()


def test_channel_half_open_peer_surfaces_as_typed_error():
    """A peer that stops responding trips the bounded recv timeout; a
    peer that dies hard (RST, no clean FIN) surfaces as ConnectionClosed
    — either way the coordinator gets a typed error, never a hang."""
    import socket
    import struct

    from repro.mining.distributed.protocol import ConnectionClosed
    from repro.mining.distributed.transport import Listener, dial

    lst = Listener()
    try:
        peer = dial(lst.address)
        chan = lst.accept(5)
        # half-open: the peer exists but never writes
        with pytest.raises(TimeoutError):
            chan.recv(0.2)
        # hard death: RST instead of FIN (SO_LINGER 0 + close), the
        # kill -9 shape — recv must type it, not crash on raw OSError
        peer.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
        peer.sock.close()
        with pytest.raises((ConnectionClosed, TimeoutError)):
            chan.recv(5)
        chan.close()
    finally:
        lst.close()


# ------------------------------------------------- rpc retry / respawn / ckpt
def test_rpc_timeout_retries_and_skips_stale_reply(tmp_path):
    """A reply that times out once is retried under a fresh seq; the
    late duplicate reply of the timed-out send is skipped as a stale
    frame, so the retry returns the right payload."""
    from repro.fault.failures import ChaosInjector, installed

    batches, n_items = _batches(21, sizes=(20,))
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="retry", n_items=n_items, workers=1, spec=SPEC, stream_spec=SSPEC,
        rpc_attempts=3, rpc_backoff_s=0.01,
    )
    try:
        dm.append(batches[0])
        # one injected timeout on the coordinator's next reply recv: the
        # worker HAS replied (the chaos fires before the socket read), so
        # the retry must discard that now-stale frame and match its own
        with installed(ChaosInjector().arm("rpc.recv", exc=TimeoutError)):
            stats = dm.worker_stats()
        assert stats[0]["stats"]["preps"] == 1  # correct payload after retry
        assert dm.stats["rpc_timeouts"] == 1
        assert dm.stats["rpc_retries"] == 1
        assert len(dm._live()) == 1  # one timeout never retires the worker
    finally:
        dm.close()


def test_rpc_retry_exhaustion_fails_over(tmp_path):
    """Every send timing out exhausts rpc_attempts and surfaces as a
    WorkerDied -> failover; with no survivors and no budget, typed
    NoLiveWorkers."""
    from repro.fault.failures import ChaosInjector, installed

    batches, n_items = _batches(22, sizes=(18, 12))
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="exhaust", n_items=n_items, workers=1, spec=SPEC, stream_spec=SSPEC,
        rpc_attempts=2, rpc_backoff_s=0.01,
    )
    try:
        dm.append(batches[0])
        inj = ChaosInjector().arm("rpc.recv", times=10**9, exc=TimeoutError)
        with installed(inj):
            with pytest.raises(NoLiveWorkers):
                dm.append(batches[1])
        assert dm.stats["rpc_timeouts"] >= 2  # both attempts timed out
        assert dm.stats["rpc_retries"] >= 1
        assert dm.stats["workers_lost"] == 1  # exhaustion ran the failover
    finally:
        dm.close()


def test_respawn_restores_pool_and_answers_exactly(tmp_path):
    """With a restart budget, a killed worker is replaced: the pool
    recovers to full size, displaced segments migrate onto the fresh
    worker snapshot-first, and answers stay bit-identical."""
    batches, n_items = _batches(23, sizes=(26, 15, 19))
    spec = SPEC.with_(min_sup=0.15)
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="respawn", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC,
        restart_budget=2,
    )
    try:
        for b in batches:
            dm.append(b)
        ref = _single_process(batches, n_items, spec)
        assert dm.mine(spec).itemsets == ref.itemsets

        victim = min(m.worker for m in dm._segments.values())
        dm.kill_worker(victim)
        res = dm.mine(spec)  # death detected mid-query -> failover+respawn
        assert res.itemsets == ref.itemsets
        assert dm.stats["respawns"] == 1
        assert dm.stats["reassign_rebuilds"] == 0  # snapshot-only recovery
        assert len(dm._live()) == 2  # pool is whole again
        live_ids = {w.wid for w in dm._live()}
        assert victim not in live_ids
        # every segment is owned by a live worker, and the fresh worker
        # actually carries load (migration happened, not just spawn)
        owners = {m.worker for m in dm._segments.values()}
        assert owners <= live_ids and max(live_ids) in owners

        # still fully serviceable, including new appends onto the new pool
        extra = random_db(np.random.default_rng(31), 12, n_items, 6)
        dm.append(extra)
        ref2 = _single_process(batches + [extra], n_items, spec)
        assert dm.mine(spec).itemsets == ref2.itemsets
    finally:
        dm.close()


def test_respawn_budget_spent_pool_shrinks(tmp_path):
    batches, n_items = _batches(24, sizes=(20, 14))
    eng = MiningEngine(snapshot_dir=str(tmp_path))
    dm = eng.distribute(
        name="budget", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC,
        restart_budget=1,
    )
    try:
        for b in batches:
            dm.append(b)
        spec = SPEC.with_(min_sup=0.2)
        ref = _single_process(batches, n_items, spec)
        for kill in range(2):
            victim = min(w.wid for w in dm._live())
            dm.kill_worker(victim)
            assert dm.mine(spec).itemsets == ref.itemsets
        assert dm.stats["respawns"] == 1  # second death: budget exhausted
        assert len(dm._live()) == 1  # now the pool has shrunk for good
    finally:
        dm.close()


def test_coordinator_checkpoint_replays_identical_database(tmp_path):
    """Restarting the coordinator from its append-log checkpoint yields
    the same SegmentedDB — same rank space, row totals, digest, and
    bit-identical answers — with segments restored from snapshots, and
    the recorded placement honored."""
    batches, n_items = _batches(25, sizes=(24, 16, 20))
    spec = SPEC.with_(min_sup=0.15)
    snap, ck = str(tmp_path / "snap"), str(tmp_path / "ck")

    eng1 = MiningEngine(snapshot_dir=snap)
    dm1 = eng1.distribute(
        name="ck", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC,
        checkpoint_dir=ck,
    )
    empty = np.full((5, 6), -1, np.int32)  # pad-only batch: rows, no segment
    try:
        for b in batches:
            dm1.append(b)
        dm1.append(empty)
        ref = dm1.mine(spec)
        placement1 = {s: m.worker for s, m in dm1._segments.items()}
        digest1 = dm1._db_digest()
        n_rows1 = dm1.db.n_rows
    finally:
        dm1.close()

    eng2 = MiningEngine(snapshot_dir=snap)
    dm2 = eng2.distribute(
        name="ck2", n_items=n_items, workers=2, spec=SPEC, stream_spec=SSPEC,
        checkpoint_dir=ck,
    )
    try:
        assert dm2.stats["restored_appends"] == len(batches) + 1
        assert dm2.db.n_rows == n_rows1
        assert dm2._db_digest() == digest1
        assert {s: m.worker for s, m in dm2._segments.items()} == placement1
        res = dm2.mine(spec)
        assert res.itemsets == ref.itemsets
        # replay was a restore, not a recompute: every segment came from
        # the shared snapshot store
        ws = dm2.worker_stats()
        assert sum(s["stats"]["seg_snapshot_hits"] for s in ws.values()) == len(batches)
        assert sum(s["stats"]["seg_prepares"] for s in ws.values()) == 0

        # the restored database keeps checkpointing: append, restart again
        extra = random_db(np.random.default_rng(41), 11, n_items, 6)
        dm2.append(extra)
        ref3 = dm2.mine(spec)
    finally:
        dm2.close()

    eng3 = MiningEngine(snapshot_dir=snap)
    dm3 = eng3.distribute(
        name="ck3", n_items=n_items, workers=1, spec=SPEC, stream_spec=SSPEC,
        checkpoint_dir=ck,
    )
    try:
        assert dm3.mine(spec).itemsets == ref3.itemsets
    finally:
        dm3.close()


def test_checkpoint_rejects_mismatched_n_items(tmp_path):
    batches, n_items = _batches(26, sizes=(15,))
    ck = str(tmp_path / "ck")
    eng = MiningEngine()
    dm = eng.distribute(
        name="ckbad", n_items=n_items, workers=1, spec=SPEC, stream_spec=SSPEC,
        checkpoint_dir=ck,
    )
    try:
        dm.append(batches[0])
    finally:
        dm.close()
    eng2 = MiningEngine()
    with pytest.raises(ValueError, match="n_items"):
        eng2.distribute(
            name="ckbad2", n_items=n_items + 1, workers=1, spec=SPEC,
            stream_spec=SSPEC, checkpoint_dir=ck,
        )
