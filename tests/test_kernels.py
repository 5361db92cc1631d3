"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import encoding as enc
from repro.core.nlist import INF, pack_nlists
from repro.core.ppc import build_ppc
from repro.data.synth import random_db
from repro.kernels.cooccur.kernel import cooccur_pallas
from repro.kernels.cooccur.ref import cooccur_ref
from repro.kernels.histogram.kernel import histogram_pallas
from repro.kernels.histogram.ref import histogram_ref
from repro.kernels.nlist_intersect.kernel import nlist_intersect_pallas
from repro.kernels.nlist_intersect.ref import nlist_intersect_ref


@pytest.mark.parametrize("R,L,n_bins", [(1, 1, 1), (7, 3, 5), (64, 8, 33), (300, 12, 129), (513, 5, 1000)])
@pytest.mark.parametrize("weighted", [False, True])
def test_histogram_sweep(R, L, n_bins, weighted):
    rng = np.random.default_rng(R * 1000 + n_bins)
    rows = rng.integers(-1, n_bins, size=(R, L)).astype(np.int32)
    w = (rng.integers(1, 5, size=R) if weighted else np.ones(R)).astype(np.int32)
    got = histogram_pallas(jnp.asarray(rows), jnp.asarray(w), n_bins=n_bins,
                           row_block=64, bin_block=128, interpret=True)
    want = histogram_ref(jnp.asarray(rows), jnp.asarray(w), n_bins=n_bins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("R,L,K", [(1, 1, 1), (9, 4, 7), (100, 6, 40), (257, 10, 130)])
def test_cooccur_sweep(R, L, K):
    rng = np.random.default_rng(R + K)
    rows = rng.integers(-1, K, size=(R, L)).astype(np.int32)
    w = rng.integers(1, 4, size=R).astype(np.int32)
    got = cooccur_pallas(jnp.asarray(rows), jnp.asarray(w), n_items=K,
                         row_block=64, k_block=64, interpret=True)
    want = cooccur_ref(jnp.asarray(rows), jnp.asarray(w), n_items=K)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _nlist_batch(rng, B, La, Ly):
    """Batches of *tree-valid* PP-codes: the kernel's contract assumes codes
    come from a real PPC-tree (antichain per item), so we sample exactly that.
    Truncation to (La, Ly) keeps validity (dropping codes only removes
    potential ancestors for both kernel and oracle alike)."""
    a_pre = np.full((B, La), INF, np.int32)
    a_post = np.full((B, La), -1, np.int32)
    y_pre = np.full((B, Ly), INF, np.int32)
    y_post = np.full((B, Ly), -1, np.int32)
    y_cnt = np.zeros((B, Ly), np.int32)
    for b in range(B):
        n_items = int(rng.integers(2, 16))
        rows = random_db(rng, int(rng.integers(5, 120)), n_items, min(8, n_items))
        fl = enc.build_flist(enc.item_support(rows, n_items), 1)
        if fl.k < 2:
            continue
        urows, w = enc.dedup_rows(enc.rank_encode(rows, fl))
        if not len(urows):
            continue
        nls = build_ppc(urows, w).nlists(fl.k)
        qa, qy = sorted(rng.choice(fl.k, size=2, replace=False))
        A, Y = nls[qa][:La], nls[qy][:Ly]
        a_pre[b, : len(A)], a_post[b, : len(A)] = A[:, 0], A[:, 1]
        y_pre[b, : len(Y)], y_post[b, : len(Y)] = Y[:, 0], Y[:, 1]
        y_cnt[b, : len(Y)] = Y[:, 2]
    return map(jnp.asarray, (a_pre, a_post, y_pre, y_post, y_cnt))


@pytest.mark.parametrize("bb", [1, 3, 8])
@pytest.mark.parametrize("B,La,Ly", [(1, 1, 1), (3, 8, 5), (5, 40, 70), (2, 130, 257)])
def test_nlist_intersect_sweep(B, La, Ly, bb):
    """Fused-kernel parity: merged counts match the oracle and the fused
    support output equals ``merged.sum(axis=1)`` — across La/Ly that are not
    block multiples and B that is not a batch_block multiple."""
    rng = np.random.default_rng(B * La + Ly)
    a_pre, a_post, y_pre, y_post, y_cnt = _nlist_batch(rng, B, La, Ly)
    got, sup = nlist_intersect_pallas(a_pre, a_post, y_pre, y_post, y_cnt,
                                      la_block=64, ly_block=64,
                                      batch_block=bb, interpret=True)
    want = nlist_intersect_ref(a_pre, a_post, y_pre, y_post, y_cnt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sup), np.asarray(want).sum(axis=1))


def test_nlist_intersect_zero_count_and_pad_slots():
    """Zero-count Y slots contribute nothing; all-PAD rows (the
    pre=INT32_MAX / post=-1 / cnt=0 sentinel convention) yield zero merged
    counts and zero support, including across batch padding."""
    rng = np.random.default_rng(7)
    B, La, Ly = 5, 24, 16
    a_pre, a_post, y_pre, y_post, y_cnt = map(
        np.asarray, _nlist_batch(rng, B, La, Ly))
    y_cnt = y_cnt.copy()
    y_cnt[1] = 0  # candidate 1: every Y slot zero-count
    a_pre, a_post = a_pre.copy(), a_post.copy()
    a_pre[2, :], a_post[2, :] = INF, -1  # candidate 2: all-PAD A list
    y_pre, y_post = y_pre.copy(), y_post.copy()
    y_pre[3, :], y_post[3, :], y_cnt[3, :] = INF, -1, 0  # candidate 3: all-PAD Y
    args = [jnp.asarray(x) for x in (a_pre, a_post, y_pre, y_post, y_cnt)]
    got, sup = nlist_intersect_pallas(*args, la_block=8, ly_block=8,
                                      batch_block=2, interpret=True)
    want = nlist_intersect_ref(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sup), np.asarray(want).sum(axis=1))
    got, sup = np.asarray(got), np.asarray(sup)
    for b in (1, 2, 3):
        assert not got[b].any() and sup[b] == 0


def test_nlist_intersect_real_tree(paper_db):
    """Kernel vs oracle on the actual paper-example N-lists."""
    rows, n_items = paper_db
    fl = enc.build_flist(enc.item_support(rows, n_items), 3)
    urows, w = enc.dedup_rows(enc.rank_encode(rows, fl))
    tree = build_ppc(urows, w)
    packed = pack_nlists(tree.nlists(fl.k), width=8)  # (K, 8, 3)
    K = fl.k
    # intersect every (a=q, y=p) pair, q < p
    pairs = [(q, p) for p in range(K) for q in range(p)]
    a = packed[[q for q, _ in pairs]]
    y = packed[[p for _, p in pairs]]
    args = [jnp.asarray(x) for x in (a[:, :, 0], a[:, :, 1], y[:, :, 0], y[:, :, 1], y[:, :, 2])]
    got, sup = nlist_intersect_pallas(*args, la_block=8, ly_block=8,
                                      batch_block=4, interpret=True)
    want = nlist_intersect_ref(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sup), np.asarray(want).sum(axis=1))
    # support(b,c) == 3 per the paper's data (rows containing both b and c)
    idx = pairs.index((0, 2))
    assert int(np.asarray(sup)[idx]) == 3


@pytest.mark.parametrize("dtype", [jnp.int32])
def test_histogram_dtype_and_shape_edge(dtype):
    # single row, single item, n_bins == 1 — degenerate tiling path
    rows = jnp.zeros((1, 1), dtype)
    got = histogram_pallas(rows, jnp.ones(1, jnp.int32), n_bins=1, interpret=True)
    assert int(got[0]) == 1


@pytest.mark.parametrize("kernel", ["histogram", "cooccur", "intersect", "intersect_es"])
def test_kernels_under_tpu_interpreter(kernel):
    """The TPU interpreter models what ``interpret=True`` cannot: an output
    block lives in VMEM only while consecutive grid programs revisit it and
    is never read back. Grids of several blocks on every axis catch a
    kernel that accumulates along a grid axis other than the innermost."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.nlist_intersect.kernel import nlist_intersect_pallas_es
    from repro.kernels.nlist_intersect.ref import nlist_intersect_masked_ref

    tpu = pltpu.InterpretParams()
    rng = np.random.default_rng(7)
    if kernel in ("histogram", "cooccur"):
        K = 300 if kernel == "histogram" else 130
        rows = jnp.asarray(rng.integers(-1, K, size=(200, 6)).astype(np.int32))
        w = jnp.asarray(rng.integers(1, 4, size=200).astype(np.int32))
        if kernel == "histogram":
            got = histogram_pallas(rows, w, n_bins=K, row_block=64, bin_block=128,
                                   interpret=tpu)
            want = histogram_ref(rows, w, n_bins=K)
        else:
            got = cooccur_pallas(rows, w, n_items=K, row_block=64, k_block=64,
                                 interpret=tpu)
            want = cooccur_ref(rows, w, n_items=K)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    a_pre, a_post, y_pre, y_post, y_cnt = _nlist_batch(rng, 16, 200, 200)
    blocks = dict(la_block=64, ly_block=64, batch_block=8, interpret=tpu)
    if kernel == "intersect":
        got = nlist_intersect_pallas(a_pre, a_post, y_pre, y_post, y_cnt, **blocks)
        want = nlist_intersect_ref(a_pre, a_post, y_pre, y_post, y_cnt)
        want = (want, want.sum(axis=1))
    else:
        a_cnt = jnp.where(a_post >= 0, 3, 0).astype(jnp.int32)
        got = nlist_intersect_pallas_es(a_pre, a_post, a_cnt, y_pre, y_post, y_cnt,
                                        12, **blocks)
        want = nlist_intersect_masked_ref(a_pre, a_post, a_cnt, y_pre, y_post, y_cnt,
                                          12, la_block=64)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
