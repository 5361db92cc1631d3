"""The least N-list bytes any exact implementation moves for one request.

PrePost's N-list of an item is the list of (pre, post, count) codes of the
PPC-tree nodes that carry it, the tree being built over the rows' frequent
items in F-list order (support descending, ties by item id ascending). An
N-list miner finds the support of an itemset of three or more items by
merging N-lists, and every merged N-list of an itemset lives on codes of
its items' own N-lists, which only prep has written to device memory. So a
request whose answer holds itemsets of three or more items reads, at the
least, each item of those itemsets' N-list once, at its true length in
12-byte codes (pre, post and count as int32), and writes one 4-byte
support per such itemset. Anything more (padding, re-reading a list at
every level or for every candidate, candidates that turn out infrequent,
the merged lists between levels) is the implementation's choice and not
counted, so no implementation of the same request moves fewer bytes than
this count.
"""
from __future__ import annotations

import numpy as np

CODE_BYTES = 12  # pre, post, count: int32 each
SUPPORT_BYTES = 4


def nlist_lengths(rows: np.ndarray, n_items: int, count: int) -> dict[int, int]:
    """{item: number of PPC-tree nodes carrying it} for the items frequent
    at ``count``. A node is a distinct prefix of a row's frequent items in
    F-list order, so level by level each node is identified by (its
    parent's node, its item's rank)."""
    rows = np.asarray(rows)
    sup = np.bincount(rows[rows >= 0].ravel(), minlength=n_items)
    keep = np.flatnonzero(sup >= count)
    order = keep[np.argsort(-sup[keep], kind="stable")]  # the F-list
    K = len(order)
    rank = np.full(n_items + 1, K, np.int64)
    rank[order] = np.arange(K)
    ranked = np.sort(rank[np.where(rows >= 0, rows, n_items)], axis=1)
    lengths = np.zeros(K, np.int64)
    node = np.zeros(len(ranked), np.int64)  # every row starts at the root
    live = np.arange(len(ranked))
    for col in range(ranked.shape[1]):
        r = ranked[live, col]
        on = r < K
        live, r = live[on], r[on]
        if not len(live):
            break
        keys, node_of = np.unique(node[live] * (K + 1) + r, return_inverse=True)
        np.add.at(lengths, keys % (K + 1), 1)
        node[live] = node_of + 1  # node ids of this depth; 0 stays the root
    return {int(order[j]): int(lengths[j]) for j in range(K)}


def least_bytes(rows: np.ndarray, n_items: int, count: int, answer: dict,
                lengths: dict[int, int] | None = None) -> int:
    """Bytes a request with this exact ``answer`` must move through the
    N-list intersections (see the module docstring). ``lengths`` may be
    passed in when several answers share one database and threshold."""
    deep = [s for s in answer if len(s) >= 3]
    if not deep:
        return 0
    if lengths is None:
        lengths = nlist_lengths(rows, n_items, count)
    items = {i for s in deep for i in s}
    return CODE_BYTES * sum(lengths[i] for i in items) + SUPPORT_BYTES * len(deep)


def per_level_bytes(rows: np.ndarray, n_items: int, count: int, answer: dict,
                    lengths: dict[int, int] | None = None) -> int:
    """The same count with each N-list read once per level instead of once
    per request: for every k >= 3, each item of the answer's k-itemsets is
    read once at its true length. A program that keeps merged lists on the
    chip from one level to the next may move less than this, so it is
    reported beside the floor, never as a roofline."""
    deep = [s for s in answer if len(s) >= 3]
    if not deep:
        return 0
    if lengths is None:
        lengths = nlist_lengths(rows, n_items, count)
    total = SUPPORT_BYTES * len(deep)
    for k in sorted({len(s) for s in deep}):
        items = {i for s in deep if len(s) == k for i in s}
        total += CODE_BYTES * sum(lengths[i] for i in items)
    return total
