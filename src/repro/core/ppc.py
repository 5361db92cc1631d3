"""Sort-based PPC-tree construction (the paper's Job-2 reduce, TPU-native).

The Hadoop reducer builds the PPC-tree by pointer insertion (``insert_tree``)
and then walks it twice to assign pre-/post-order ranks. Pointer tries do not
vectorize, so we construct the *identical* tree algebraically:

1. Lexicographically sort the rank-encoded transactions. In a prefix tree
   built from sorted rows, every trie node corresponds to a *distinct row
   prefix*, and the rows sharing that prefix are contiguous.
2. A node of depth ``d+1`` starts at row ``i`` iff column ``d`` is valid and
   the length-``d+1`` prefix differs from row ``i-1`` (vectorized cumulative
   OR of per-column inequality).
3. Flattening the boundary mask row-major enumerates nodes sorted by
   ``(start_row, depth)`` — which *is* pre-order (DFS of sorted rows).
4. A node's subtree is the nodes after it that start before its end row
   ``end``. Pre-order numbers are row-major over the boundary mask, so
   ``pre + size`` is the number of nodes in rows ``0..end-1``: a prefix sum
   of per-row node counts read at ``end``, no search. The closed form
   ``post = pre + size - 1 - depth`` replaces the post-order traversal.
5. ``count`` = windowed sum of row weights over the node's row range.

The result is bit-identical to the pointer-built tree (property-tested
against ``_build_ppc_pointer`` below) but is all sorts/scans/gathers — the
shape of computation TPUs execute well, and the same code runs inside
``shard_map`` for the distributed miner (each shard owns its block's tree,
exactly like one Hadoop reducer).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import PAD


@dataclasses.dataclass
class PPCTree:
    """Flat PPC-tree: one row per node, pre-order sorted."""

    item: np.ndarray  # (N,) F-list rank registered by the node
    count: np.ndarray  # (N,) transactions through the node
    pre: np.ndarray  # (N,) pre-order rank == arange(N)
    post: np.ndarray  # (N,) post-order rank
    depth: np.ndarray  # (N,) 0-indexed depth (top-level nodes = 0)
    n_nodes: int

    def nlists(self, k: int) -> list[np.ndarray]:
        """Per-item N-lists: (len_i, 3) arrays of (pre, post, count), pre-asc.

        Nodes registering one item are an antichain (items are unique along
        any root path), so each list's pre-order intervals are disjoint —
        the property the vectorized intersection relies on.
        """
        order = np.argsort(self.item, kind="stable")  # stable keeps pre-order
        out: list[np.ndarray] = []
        bounds = np.searchsorted(self.item[order], np.arange(k + 1))
        packed = np.stack([self.pre, self.post, self.count], axis=1)
        for i in range(k):
            out.append(packed[order[bounds[i] : bounds[i + 1]]])
        return out


def build_ppc(rows: np.ndarray, weights: np.ndarray | None = None) -> PPCTree:
    """Host/numpy sort-based construction. ``rows`` rank-encoded, PAD=-1."""
    rows = np.asarray(rows, np.int32)
    R, L = rows.shape
    w = np.ones(R, np.int64) if weights is None else np.asarray(weights, np.int64)
    if R == 0:
        z = np.zeros(0, np.int64)
        return PPCTree(z, z, z, z, z, 0)

    order = np.lexsort(tuple(rows[:, c] for c in range(L - 1, -1, -1)))
    srows = rows[order]
    sw = w[order]

    valid = srows != PAD
    neq = np.ones_like(valid)
    neq[1:] = srows[1:] != srows[:-1]
    chg = np.logical_or.accumulate(neq, axis=1)  # prefix(d+1) differs from prev row
    newgrp = valid & chg

    # next row (strictly after i) where prefix of this depth changes
    idx = np.where(chg, np.arange(R)[:, None], R)
    nxt = np.minimum.accumulate(idx[::-1], axis=0)[::-1]
    nxt = np.vstack([nxt[1:], np.full((1, L), R, np.int64)])  # strict successor

    pos = np.flatnonzero(newgrp.ravel())  # row-major == (start_row, depth) == pre-order
    start = pos // L
    depth = pos % L
    end = nxt[start, depth]  # exclusive row end of the node's range

    wsum = np.concatenate([[0], np.cumsum(sw)])
    count = wsum[end] - wsum[start]
    item = srows[start, depth].astype(np.int64)

    n = len(pos)
    pre = np.arange(n, dtype=np.int64)
    size = np.searchsorted(start, end, side="left") - pre  # subtree is pre-order contiguous
    post = pre + size - 1 - depth
    return PPCTree(item=item, count=count, pre=pre, post=post, depth=depth.astype(np.int64), n_nodes=n)


def build_ppc_jnp(rows: jnp.ndarray, weights: jnp.ndarray, max_nodes: int, n_items: int = 0):
    """Jit-able construction with static output size ``max_nodes``.

    Returns ``(item, count, pre, post, valid_mask)`` padded to ``max_nodes``
    (invalid slots: item = -1, count = 0, pre = big). Used by HPrepost inside
    ``shard_map``; on a shard of R rows × L cols, ``max_nodes`` ≤ R·L.

    ``n_items``: when the rank alphabet is known and small, pairs of columns
    are packed into single int32 sort keys (lexicographically equivalent) —
    halves the lexsort key count, which dominates compile+run time at L≈74.
    """
    R, L = rows.shape
    if 0 < n_items <= 30_000 and L > 8:
        base = n_items + 2
        shifted = rows + 1  # PAD -> 0 keeps order
        if L % 2:
            shifted = jnp.pad(shifted, ((0, 0), (0, 1)))
        packed = shifted[:, 0::2] * base + shifted[:, 1::2]
        keys = tuple(packed[:, c] for c in range(packed.shape[1] - 1, -1, -1))
    else:
        keys = tuple(rows[:, c] for c in range(L - 1, -1, -1))
    # lexsort as stable one-key sorts, least significant key first: the same
    # permutation as ``jnp.lexsort(keys)``, but the TPU compiler's time for
    # one multi-key sort grows steeply with the key count (beyond ten
    # minutes at 24 keys), while each one-key sort compiles in about a second
    order = jnp.arange(R)
    for key in keys:
        order = order[jnp.argsort(key[order], stable=True)]
    srows = rows[order]
    sw = weights[order]

    valid = srows != PAD
    neq = jnp.concatenate([jnp.ones((1, L), bool), srows[1:] != srows[:-1]], axis=0)
    chg = jax.lax.cummax(neq.astype(jnp.int32), axis=1).astype(bool)
    newgrp = valid & chg

    idx = jnp.where(chg, jnp.arange(R)[:, None], R)
    nxt = jax.lax.cummin(idx, axis=0, reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1, L), R, idx.dtype)], axis=0)

    flat = newgrp.ravel()
    # stable "nonzero with static size": sort flat positions, valid first
    keys = jnp.where(flat, jnp.arange(R * L), R * L)
    pos = jnp.sort(keys)[:max_nodes]
    node_valid = pos < R * L
    pos = jnp.where(node_valid, pos, 0)
    start = pos // L
    depth = pos % L
    end = nxt[start, depth]

    wsum = jnp.concatenate([jnp.zeros(1, sw.dtype), jnp.cumsum(sw)])
    count = jnp.where(node_valid, wsum[end] - wsum[start], 0)
    item = jnp.where(node_valid, srows[start, depth], -1)

    pre = jnp.arange(max_nodes)
    # rowcum[r]: nodes starting in rows before r, capped at the slots kept.
    # Pre-order is row-major over ``newgrp``, so rowcum[end] = pre + size.
    per_row = newgrp.sum(axis=1, dtype=jnp.int32)
    rowcum = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(per_row)])
    size = jnp.minimum(rowcum, max_nodes)[end] - pre
    post = jnp.where(node_valid, pre + size - 1 - depth, jnp.iinfo(jnp.int32).max)
    pre = jnp.where(node_valid, pre, jnp.iinfo(jnp.int32).max)
    return item, count, pre, post, node_valid


# --------------------------------------------------------------------------
# Pointer-based oracle (the paper's literal insert_tree) — tests only.
# --------------------------------------------------------------------------


def _build_ppc_pointer(rows: np.ndarray, weights: np.ndarray | None = None) -> PPCTree:
    """Literal Algorithm-1 ``insert_tree`` + two traversals. O(R·L) pointers."""
    R, L = rows.shape
    w = np.ones(R, np.int64) if weights is None else np.asarray(weights, np.int64)
    root: dict = {"item": None, "count": 0, "children": {}}
    for r in range(R):
        node = root
        for c in range(L):
            it = int(rows[r, c])
            if it == PAD:
                break
            child = node["children"].get(it)
            if child is None:
                child = {"item": it, "count": 0, "children": {}}
                node["children"][it] = child
            child["count"] += int(w[r])
            node = child

    items, counts, pres, posts, depths = [], [], [], [], []
    pre_ctr = [0]
    post_ctr = [0]

    def visit(node, depth):
        my = len(items)
        items.append(node["item"])
        counts.append(node["count"])
        depths.append(depth)
        pres.append(pre_ctr[0])
        posts.append(-1)
        pre_ctr[0] += 1
        for it in sorted(node["children"]):  # children in item order == sorted-row DFS
            visit(node["children"][it], depth + 1)
        posts[my] = post_ctr[0]
        post_ctr[0] += 1

    for it in sorted(root["children"]):
        visit(root["children"][it], 0)
    return PPCTree(
        item=np.array(items, np.int64),
        count=np.array(counts, np.int64),
        pre=np.array(pres, np.int64),
        post=np.array(posts, np.int64),
        depth=np.array(depths, np.int64),
        n_nodes=len(items),
    )
