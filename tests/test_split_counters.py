"""The data split's span and counters: ``prep.split``, the per-shard N-list
node count, the packed slots and the exchange payload.

Everything runs in one child with four forced CPU devices (the device
count is fixed when JAX starts), once on a 4x1 ``(data, model)`` mesh and
once on a 1x1 mesh of its first device; the child prints what it read as
JSON and the tests check it against host recomputations.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

N_ROWS, N_ITEMS, MAX_LEN, MIN_COUNT = 203, 14, 7, 6

_CHILD = textwrap.dedent(
    f"""
    import json
    import numpy as np, jax
    from jax.sharding import AxisType
    from repro.core import encoding as enc
    from repro.core.hprepost import HPrepostConfig, HPrepostMiner
    from repro.core.ppc import build_ppc
    from repro.core.prepost import mine_prepost
    from repro.data.synth import random_db
    from repro.mining import MineSpec
    from repro.mining.engine import MiningEngine
    from repro.mining.telemetry import TraceRecorder, trace

    rows = random_db(np.random.default_rng(3), {N_ROWS}, {N_ITEMS}, {MAX_LEN})
    spec = MineSpec(algorithm="hprepost", min_count={MIN_COUNT}, max_k=4, candidate_unit=8)
    ref = mine_prepost(rows, {N_ITEMS}, {MIN_COUNT}).itemsets
    out = {{}}
    for D in (4, 1):
        mesh = jax.make_mesh((D, 1), ("data", "model"), devices=jax.devices()[:D],
                             axis_types=(AxisType.Auto,) * 2)
        miner = HPrepostMiner(mesh, config=HPrepostConfig(candidate_unit=8))
        rec = TraceRecorder()
        with trace.attached(rec):
            prepared = miner.prepare(rows, {N_ITEMS}, {MIN_COUNT})
        spans = sorted(([s["name"], s["t0"], s["t1"]] for s in rec.spans.values()
                        if s["name"].startswith("prep.")), key=lambda s: s[1])
        # each shard's PPC-tree built on the host from its block of rows
        Rp = -(-len(rows) // D) * D
        padded = np.full((Rp, rows.shape[1]), enc.PAD, np.int32)
        padded[:len(rows)] = rows
        trees = [build_ppc(enc.rank_encode(b, prepared.fl)) for b in np.split(padded, D)]
        eng = MiningEngine(mesh=mesh)
        first, cached = eng.submit(rows, {N_ITEMS}, spec), eng.submit(rows, {N_ITEMS}, spec)
        out[D] = dict(
            nlist_nodes=prepared.nlist_nodes, nlist_slots=prepared.nlist_slots,
            width=prepared.width, k=prepared.fl.k,
            prep_exchange=prepared.stage_times["exchange_bytes"],
            host_nodes=[t.n_nodes for t in trees],
            host_longest=max(int(np.bincount(t.item, minlength=prepared.fl.k).max())
                             for t in trees),
            spans=spans,
            results=[dict(stages=r.stage_times_s, prep_source=r.service_stats["prep_source"],
                          exact=r.itemsets == ref) for r in (first, cached)],
        )
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def read():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))}
    p = subprocess.run([sys.executable, "-c", _CHILD], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {int(d): v for d, v in json.loads(p.stdout.strip().splitlines()[-1]).items()}


@pytest.mark.parametrize("D", [4, 1])
def test_nlist_nodes_are_the_per_shard_trees_nodes(read, D):
    r = read[D]
    assert len(r["host_nodes"]) == D
    assert r["nlist_nodes"] == sum(r["host_nodes"]) > 0
    assert r["nlist_slots"] == D * r["k"] * r["width"]
    for res in r["results"]:  # built, then from the cache: both carry them
        assert res["stages"]["nlist_nodes"] == r["nlist_nodes"]
        assert res["stages"]["nlist_slots"] == r["nlist_slots"]
    assert [res["prep_source"] for res in r["results"]] == ["built", "cache"]


def test_four_shards_hold_more_nodes_than_one_tree(read):
    # four small trees share fewer prefixes than one large tree
    assert read[4]["nlist_nodes"] > read[1]["nlist_nodes"]


@pytest.mark.parametrize("D", [4, 1])
def test_width_is_the_power_of_two_above_the_longest_list_on_any_shard(read, D):
    r = read[D]
    width = 1
    while width < max(r["host_longest"], 8):
        width *= 2
    assert r["width"] == width


def test_exchange_is_the_reductions_payload_on_four_shards(read):
    r = read[4]
    prep = N_ITEMS * 4 + r["k"] * r["k"] * 4  # Job 1's histogram, F2's matrix
    assert r["prep_exchange"] == prep
    built, cached = (res["stages"] for res in r["results"])
    assert built["wave_slots"] > 0
    # each wave's support vector (Cpad int32 supports, one model shard);
    # a request served from the cache counts only its waves
    assert built["exchange_bytes"] == prep + 4 * built["wave_slots"]
    assert cached["exchange_bytes"] == 4 * cached["wave_slots"]


def test_exchange_is_zero_on_one_shard(read):
    r = read[1]
    assert r["prep_exchange"] == 0
    assert [res["stages"]["exchange_bytes"] for res in r["results"]] == [0, 0]


@pytest.mark.parametrize("D", [4, 1])
def test_split_span_closes_before_job1_opens(read, D):
    names = [s[0] for s in read[D]["spans"]]
    assert names == ["prep.split", "prep.job1", "prep.job2", "prep.pack", "prep.f2"]
    (_, _, split_end), (_, job1_start, _) = read[D]["spans"][:2]
    assert split_end <= job1_start


@pytest.mark.parametrize("D", [4, 1])
def test_answers_still_equal_prepost(read, D):
    assert all(res["exact"] for res in read[D]["results"])



def test_a_snapshot_restored_prep_carries_the_same_nlist_counters(tmp_path):
    from repro.data.synth import random_db
    from repro.mining import MineSpec
    from repro.mining.engine import MiningEngine

    rows = random_db(np.random.default_rng(3), N_ROWS, N_ITEMS, MAX_LEN)
    spec = MineSpec(algorithm="hprepost", min_count=MIN_COUNT, max_k=4, candidate_unit=8)
    cold = MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, N_ITEMS, spec)
    warm = MiningEngine(snapshot_dir=str(tmp_path)).submit(rows, N_ITEMS, spec)
    assert warm.service_stats["prep_source"] == "snapshot"
    for key in ("nlist_nodes", "nlist_slots"):
        assert warm.stage_times_s[key] == cold.stage_times_s[key] > 0
