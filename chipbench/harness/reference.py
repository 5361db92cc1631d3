"""The plain reference: every frequent itemset of a database, exactly.

Level-wise Apriori over vertical bitmaps, written for this benchmark and
independent of the program: each frequent item gets one bit per row, a
candidate of size k joins two frequent (k-1)-itemsets that share their
first k-2 items, is kept only when every (k-1)-subset is frequent, and its
support is the population count of the AND of its parent's and its last
item's bitmaps. The semantics are the program's contract: an itemset is
frequent iff its support is at least ``min_count(min_sup, rows)``.
"""
from __future__ import annotations

import math

import numpy as np

CHUNK = 1 << 26  # bitmap bytes ANDed per step: bounds the temporaries


def min_count(min_sup: float, n_rows: int) -> int:
    """Ceiling semantics: support / rows >= min_sup (1e-9 float slack)."""
    return max(1, math.ceil(min_sup * n_rows - 1e-9))


def _bitmaps(rows: np.ndarray, items: np.ndarray, n_items: int) -> np.ndarray:
    """(len(items), ceil(R/64)) uint64: bit r of row i is set iff
    transaction r holds items[i]."""
    R = len(rows)
    col = np.full(n_items, -1, np.int64)
    col[items] = np.arange(len(items))
    r, c = np.nonzero(rows >= 0)
    which = col[rows[r, c]]
    keep = which >= 0
    r, which = r[keep], which[keep]
    bits = np.zeros((len(items), -(-R // 64) * 64), bool)
    bits[which, r] = True
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint64)


def _popcounts(left: np.ndarray, li: np.ndarray, right: np.ndarray,
               ri: np.ndarray) -> np.ndarray:
    """popcount(left[li[j]] & right[ri[j]]) for every j, over (n, words)
    uint64 bitmaps, gathered a chunk at a time."""
    out = np.empty(len(li), np.int64)
    step = max(1, CHUNK // max(1, left.shape[1] * 8))
    for s in range(0, len(li), step):
        both = left[li[s:s + step]] & right[ri[s:s + step]]
        out[s:s + step] = np.bitwise_count(both).sum(axis=1, dtype=np.int64)
    return out


def frequent_itemsets(rows: np.ndarray, n_items: int, count: int,
                      max_k: int | None) -> dict[tuple[int, ...], int]:
    """Every itemset of at most ``max_k`` items with support >= ``count``,
    as sorted item-id tuples mapped to exact supports."""
    rows = np.asarray(rows)
    sup = np.bincount(rows[rows >= 0].ravel(), minlength=n_items)
    items = np.flatnonzero(sup >= count)
    out = {(int(i),): int(sup[i]) for i in items}
    if max_k == 1 or len(items) < 2:
        return out
    item_bm = _bitmaps(rows, items, n_items)
    pos = {int(it): j for j, it in enumerate(items)}
    # level state: sorted tuples of frequent (k-1)-itemsets and their bitmaps
    level = [(int(i),) for i in items]
    level_bm = item_bm
    k = 2
    while level and (max_k is None or k <= max_k):
        index = {s: j for j, s in enumerate(level)}
        parents, lasts, cands = [], [], []
        # join: itemsets sharing all but their last item, in sorted order
        by_prefix: dict[tuple, list[int]] = {}
        for j, s in enumerate(level):
            by_prefix.setdefault(s[:-1], []).append(j)
        for members in by_prefix.values():
            for a_i, a in enumerate(members):
                for b in members[a_i + 1:]:
                    cand = level[a] + (level[b][-1],)
                    if k > 2 and any(cand[:d] + cand[d + 1:] not in index
                                     for d in range(k - 2)):
                        continue
                    parents.append(a)
                    lasts.append(pos[cand[-1]])
                    cands.append(cand)
        if not cands:
            break
        parents = np.asarray(parents)
        lasts = np.asarray(lasts)
        counts = _popcounts(level_bm, parents, item_bm, lasts)
        keep = np.flatnonzero(counts >= count)
        level = [cands[j] for j in keep]
        for j in keep:
            out[cands[j]] = int(counts[j])
        level_bm = level_bm[parents[keep]] & item_bm[lasts[keep]]
        k += 1
    return out


def rounded_to_bfloat16(answer: dict, count: int) -> dict:
    """The control: the reference's supports carried as bfloat16 values, as
    one bf16 pass of the MXU carries counts, then thresholded again. Runs on
    the default JAX device."""
    import jax.numpy as jnp

    keys = list(answer)
    sups = jnp.asarray(np.array([answer[s] for s in keys], np.float32))
    approx = np.asarray(sups.astype(jnp.bfloat16).astype(jnp.float32)).astype(np.int64)
    return {s: int(v) for s, v in zip(keys, approx) if v >= count}
