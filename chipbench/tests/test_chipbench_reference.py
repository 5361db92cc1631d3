"""The benchmark's own generators and reference against plain loops."""
import itertools
import zlib

import numpy as np
import pytest

from chipbench.harness import data, reference

KOSARAK = {"name": "kosarak", "kind": "sparse", "n_rows": 3000, "n_items": 500,
           "avg_len": 8, "max_len": 48, "assumed": {"data_seed": 0, "zipf_a": 1.35}}


def _sparse_loop(cfg, rng, n_tx):
    """The generator as a per-row loop: the form the vectorised copy keeps."""
    lens = np.minimum(rng.geometric(1.0 / cfg["avg_len"], size=n_tx), cfg["max_len"])
    total = int(lens.sum())
    items = rng.zipf(1.35, size=total * 2)
    items = items[items <= cfg["n_items"]][:total].astype(np.int64) - 1
    while len(items) < total:
        extra = rng.zipf(1.35, size=total)
        extra = extra[extra <= cfg["n_items"]]
        items = np.concatenate([items, extra.astype(np.int64) - 1])[:total]
    out = np.full((n_tx, cfg["max_len"]), -1, np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)])
    for r in range(n_tx):
        seg = np.unique(items[starts[r]:starts[r + 1]])
        out[r, :len(seg)] = seg
    return out


def test_sparse_generator_matches_the_loop():
    seed = zlib.crc32(b"kosarak") % 2**16
    got = data.sparse_rows(KOSARAK, np.random.default_rng(seed), 3000)
    want = _sparse_loop(KOSARAK, np.random.default_rng(seed), 3000)
    np.testing.assert_array_equal(got, want)


def _brute(rows, n_items, count, max_k):
    sets = [set(int(x) for x in r if x >= 0) for r in rows]
    out = {}
    items = sorted({i for s in sets for i in s})
    for k in range(1, max_k + 1):
        found = False
        for cand in itertools.combinations(items, k):
            sup = sum(1 for s in sets if s.issuperset(cand))
            if sup >= count:
                out[cand] = sup
                found = True
        if not found:
            break
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    rows = np.full((60, 6), -1, np.int32)
    for r in range(60):
        n = rng.integers(0, 7)
        rows[r, :n] = rng.choice(9, size=n, replace=False)
    for count in (3, 8):
        assert reference.frequent_itemsets(rows, 9, count, 4) == _brute(rows, 9, count, 4)


def test_min_count_is_the_ceiling():
    assert reference.min_count(0.13, 8124) == 1057
    assert reference.min_count(3 / 7, 7) == 3
    assert reference.min_count(0.25, 10) == 3
