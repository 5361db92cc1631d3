"""PPC-tree construction: paper example, sort-based vs pointer oracle, and
the jit-able Job 2 builder on the inputs Job 2 sees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import encoding as enc
from repro.core.ppc import _build_ppc_pointer, build_ppc, build_ppc_jnp
from repro.data.synth import random_db

INT32_MAX = np.iinfo(np.int32).max


def _ranked(rows, n_items, min_count):
    fl = enc.build_flist(enc.item_support(rows, n_items), min_count)
    return enc.dedup_rows(enc.rank_encode(rows, fl)), fl


def test_paper_example(paper_db):
    """Fig. 1 / Fig. 2 of the paper (rootless codes: paper pre = ours + 1)."""
    rows, n_items = paper_db
    (urows, w), fl = _ranked(rows, n_items, 3)
    assert list(fl.items) == [1, 0, 2, 3, 4]  # F-list: b a c d e
    assert list(fl.supports) == [5, 4, 3, 3, 3]
    tree = build_ppc(urows, w)
    nls = tree.nlists(fl.k)
    # paper N-list of b: (1,5):5  -> rootless (0,5):5
    assert nls[0].tolist() == [[0, 5, 5]]
    # paper N-list of d: {5,2}:1, {8,7}:2 -> (4,2):1, (7,7):2
    assert nls[3].tolist() == [[4, 2, 1], [7, 7, 2]]
    # paper N-list of e: (3,0):1 (6,3):1 (9,6):1 -> shifted by 1
    assert nls[4].tolist() == [[2, 0, 1], [5, 3, 1], [8, 6, 1]]


@settings(max_examples=60, deadline=None)
@given(
    n_tx=st.integers(1, 60),
    n_items=st.integers(1, 20),
    max_len=st.integers(1, 10),
    seed=st.integers(0, 2**31 - 1),
)
def test_sort_based_equals_pointer(n_tx, n_items, max_len, seed):
    rng = np.random.default_rng(seed)
    rows = random_db(rng, n_tx, n_items, min(max_len, n_items))
    (urows, w), _ = _ranked(rows, n_items, 1)
    if len(urows) == 0:
        return
    a = build_ppc(urows, w)
    b = _build_ppc_pointer(urows, w)
    assert a.n_nodes == b.n_nodes
    for f in ("item", "count", "pre", "post", "depth"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)


@settings(max_examples=25, deadline=None)
@given(
    n_tx=st.integers(1, 40),
    n_items=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
def test_jnp_build_matches_numpy(n_tx, n_items, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    rows = random_db(rng, n_tx, n_items, min(6, n_items))
    (urows, w), _ = _ranked(rows, n_items, 1)
    if len(urows) == 0:
        return
    ref = build_ppc(urows, w)
    max_nodes = urows.size
    item, count, pre, post, valid = build_ppc_jnp(
        jnp.asarray(urows), jnp.asarray(w), max_nodes
    )
    n = int(valid.sum())
    assert n == ref.n_nodes
    np.testing.assert_array_equal(np.asarray(item)[:n], ref.item)
    np.testing.assert_array_equal(np.asarray(count)[:n], ref.count)
    np.testing.assert_array_equal(np.asarray(pre)[:n], ref.pre)
    np.testing.assert_array_equal(np.asarray(post)[:n], ref.post)


def _job2_rows(rng, n_tx, n_items, max_len, min_count, dup, pad_rows):
    """Rank-encoded rows as Job 2 gets them: not deduped, PAD tails, plus
    ``dup`` repeated rows and ``pad_rows`` all-PAD rows, shuffled in."""
    rows = random_db(rng, n_tx, n_items, max_len)
    fl = enc.build_flist(enc.item_support(rows, n_items), min_count)
    ranked = enc.rank_encode(rows, fl)
    extra = [ranked[rng.integers(0, n_tx, dup)], np.full((pad_rows, max_len), enc.PAD, np.int32)]
    ranked = np.concatenate([ranked, *extra])
    return ranked[rng.permutation(len(ranked))], fl.k


# (n_tx, n_items, max_len, min_count, dup, pad_rows, packed, zero_weights)
JOB2_CASES = {
    "duplicate_rows": (40, 10, 6, 1, 40, 0, False, False),
    "all_pad_rows": (30, 12, 5, 1, 0, 20, False, False),
    "packed_even_L": (50, 30, 10, 2, 10, 5, True, False),
    "packed_odd_L": (50, 30, 11, 2, 10, 5, True, False),
    "mostly_invalid_slots": (60, 40, 24, 15, 0, 60, True, False),
    "zero_weights": (40, 10, 6, 1, 20, 5, False, True),
}


@pytest.mark.parametrize("case", list(JOB2_CASES))
def test_jnp_build_matches_pointer_on_job2_inputs(case, rng):
    n_tx, n_items, max_len, min_count, dup, pad_rows, packed, zero_w = JOB2_CASES[case]
    rows, k = _job2_rows(rng, n_tx, n_items, max_len, min_count, dup, pad_rows)
    w = rng.integers(0, 3, len(rows)).astype(np.int32) if zero_w else np.ones(len(rows), np.int32)
    if zero_w:
        assert (w == 0).any()
    max_nodes = rows.size  # Job 2's R·L
    out = build_ppc_jnp(jnp.asarray(rows), jnp.asarray(w), max_nodes, n_items=k if packed else 0)
    item, count, pre, post, valid = map(np.asarray, out)

    ref = _build_ppc_pointer(rows, w)
    n = ref.n_nodes
    assert 0 < n < max_nodes
    np.testing.assert_array_equal(valid, np.arange(max_nodes) < n)
    for name, got in (("item", item), ("count", count), ("pre", pre), ("post", post)):
        np.testing.assert_array_equal(got[:n], getattr(ref, name), err_msg=name)
    # invalid slots hold the sentinels the pack stage relies on
    assert (item[n:] == -1).all() and (count[n:] == 0).all()
    assert (pre[n:] == INT32_MAX).all() and (post[n:] == INT32_MAX).all()
    if case == "mostly_invalid_slots":
        assert n < max_nodes // 4


def test_jnp_build_has_no_loop():
    """Subtree sizes come from a prefix sum, not a search: the lowered Job 2
    builder at a stream segment's width holds no ``while`` loop."""
    rows = jnp.zeros((64, 23), jnp.int32)
    w = jnp.ones(64, jnp.int32)
    build = jax.jit(build_ppc_jnp, static_argnames=("max_nodes", "n_items"))
    text = build.lower(rows, w, max_nodes=64 * 23, n_items=119).as_text()
    assert "stablehlo.sort" in text  # lowered the packed lexsort, not a stub
    assert "stablehlo.while" not in text


def test_subtree_interval_invariants(rng):
    """Pre/post codes must encode ancestry: disjoint-or-nested intervals."""
    rows = random_db(rng, 80, 15, 8)
    (urows, w), _ = _ranked(rows, 15, 1)
    t = build_ppc(urows, w)
    # root-level counts sum to number of (nonempty) weighted rows
    top = t.depth == 0
    assert t.count[top].sum() == w[(urows != enc.PAD).any(axis=1)].sum()
    # ancestry iff (pre <, post >): check transitivity-free pairwise coherence
    pre, post = t.pre, t.post
    anc = (pre[:, None] < pre[None, :]) & (post[:, None] > post[None, :])
    # a node never "crosses" another: either nested or disjoint
    crossing = (pre[:, None] < pre[None, :]) & (post[:, None] < post[None, :]) & (
        pre[None, :] < post[:, None] + 1
    )
    # crossing in interval terms is impossible for a tree encoding
    for i, j in zip(*np.nonzero(anc)):
        assert t.depth[i] < t.depth[j]
