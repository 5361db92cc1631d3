"""Pallas TPU kernel: batched N-list intersection fused with support
reduction (the paper's Example 2 + the support count that follows it).

For a batch of candidate itemsets, merges the candidate's N-list ``Y``
(codes of its base item with current counts) into the extension item's
N-list ``A``: ``out[b, i] = Σ_j y_cnt[b, j] · [a_pre[b, i] < y_pre[b, j]]
· [a_post[b, i] > y_post[b, j]]``, and — fused in the same pass —
``support[b] = Σ_i out[b, i]``. Producing the support inside the kernel
removes the second full HBM read of the merged state that a post-kernel
``sum(axis=1)`` costs per mining wave.

Hardware adaptation (GPU/CPU -> TPU): the paper's linear merge — and even
the searchsorted form used on host — is a gather/branch pattern TPUs
execute poorly. Because each ``y`` has at most one ancestor in ``A``
(antichain property, see nlist.py), the merge is *equivalent* to a dense
subsume-mask contraction, which is a matmul: build the boolean mask in
VMEM with two broadcast compares and contract against ``y_cnt`` on the
MXU. O(La·Ly) arithmetic beats O(Ly·log La) gathers on a systolic array
by a wide margin at N-list sizes (≤ few thousand codes).

Fused-output tiling: the grid is (B/bb, La/la, Ly/ly), Ly-major (the last
grid axis iterates fastest), with ``bb`` candidates per program. Each
program builds the (bb, la, ly) subsume mask and issues one *stacked*
MXU contraction — (bb·la, ly) × (ly, bb) — instead of ``bb`` separate
(la, ly) × (ly, 1) matvecs; the candidate-diagonal block of the result is
the (bb, la) merged-count tile. The merged tile accumulates across the Ly
grid axis (revisited output block, consecutive in traversal order); the
(bb, 1) support tile additionally accumulates across the La axis, so both
outputs leave one ``pallas_call``.

Counts are fp32 in-kernel: exact for values < 2^24. Itemset supports are
bounded by the per-shard row count, which ``HPrepostMiner.prepare``
guards against that bound before any wave is dispatched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _intersect_kernel(
    a_pre_ref, a_post_ref, y_pre_ref, y_post_ref, y_cnt_ref, out_ref, sup_ref
):
    lab_i = pl.program_id(1)
    lyb_j = pl.program_id(2)

    @pl.when(lyb_j == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((lab_i == 0) & (lyb_j == 0))
    def _init_sup():
        sup_ref[...] = jnp.zeros_like(sup_ref)

    a_pre = a_pre_ref[...]  # (bb, la)
    a_post = a_post_ref[...]  # (bb, la)
    y_pre = y_pre_ref[...]  # (bb, ly)
    y_post = y_post_ref[...]  # (bb, ly)
    y_cnt = y_cnt_ref[...].astype(jnp.float32)  # (bb, ly)
    bb, la = a_pre.shape
    ly = y_pre.shape[1]

    # subsume mask (bb, la, ly): A[b, i] is an ancestor of Y[b, j]
    mask = (a_pre[:, :, None] < y_pre[:, None, :]) & (
        a_post[:, :, None] > y_post[:, None, :]
    )
    # stacked contraction (bb·la, ly) × (ly, bb): one MXU matmul per program;
    # r[b, i, c] = Σ_j mask[b, i, j] · y_cnt[c, j] — only the candidate
    # diagonal c == b is wanted, and with bb ≤ the MXU's 128 output columns
    # the cross terms ride along for free where a matvec would idle them.
    r = jax.lax.dot_general(
        mask.astype(jnp.float32).reshape(bb * la, ly),
        y_cnt,
        (((1,), (1,)), ((), ())),
        # counts above 256 are not exact in one bf16 MXU pass; the fp32
        # contraction keeps every count below 2^24 exact
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(bb, la, bb)
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (bb, bb), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (bb, bb), 1)
    ).astype(jnp.float32)
    part = jnp.sum(r * eye[:, None, :], axis=2)  # (bb, la)
    out_ref[...] += part
    sup_ref[...] += part.sum(axis=1, keepdims=True)


def _intersect_es_kernel(
    stop_ref, rem_ref, a_pre_ref, a_post_ref, y_pre_ref, y_post_ref, y_cnt_ref,
    out_ref, sup_ref,
):
    """Early-stopping variant (arXiv:1901.07773 brought on-grid): each
    program re-derives per-candidate liveness from the accumulating support
    and the inclusive A-count suffix mass of the remaining row tiles, and
    masks dead candidates out of every later tile.

    The bound is anti-monotone over the grid's Ly-major traversal: a dead
    candidate's contributions are zeroed, which freezes its support, while
    ``rem`` only shrinks with the tile index — so the liveness predicate is
    stable within a tile and monotone across tiles, and no scratch state is
    needed. With ``stop <= 0`` every candidate stays alive and the
    arithmetic (a multiply by 1.0) matches the exact kernel bit-for-bit.

    Soundness of the bound: Y-nodes below one A-slot form an antichain in
    that slot's subtree (same-item PP codes), so a tile's merged mass never
    exceeds its A-count mass — support-so-far plus remaining A-mass is a
    true upper bound on the final support.
    """
    lab_i = pl.program_id(1)
    lyb_j = pl.program_id(2)

    @pl.when(lyb_j == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((lab_i == 0) & (lyb_j == 0))
    def _init_sup():
        sup_ref[...] = jnp.zeros_like(sup_ref)

    # (bb, 1): final support <= support so far + A-count mass of tiles i..
    # ``rem_ref`` holds every A-tile's suffix mass (a block narrower than
    # the full tile axis would break the TPU's (8, 128) tiling); pick this
    # tile's column with a lane mask, which lowers to plain vector selects
    rem_all = rem_ref[...]  # (bb, nt)
    col = jax.lax.broadcasted_iota(jnp.int32, rem_all.shape, 1)
    rem = jnp.sum(jnp.where(col == lab_i, rem_all, 0.0), axis=1, keepdims=True)
    alive = (sup_ref[...] + rem) >= stop_ref[0, 0]

    @pl.when(jnp.max(alive.astype(jnp.int32)) > 0)
    def _compute():
        a_pre = a_pre_ref[...]  # (bb, la)
        a_post = a_post_ref[...]
        y_pre = y_pre_ref[...]  # (bb, ly)
        y_post = y_post_ref[...]
        y_cnt = y_cnt_ref[...].astype(jnp.float32)
        bb, la = a_pre.shape
        ly = y_pre.shape[1]
        mask = (a_pre[:, :, None] < y_pre[:, None, :]) & (
            a_post[:, :, None] > y_post[:, None, :]
        )
        r = jax.lax.dot_general(
            mask.astype(jnp.float32).reshape(bb * la, ly),
            y_cnt,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).reshape(bb, la, bb)
        eye = (
            jax.lax.broadcasted_iota(jnp.int32, (bb, bb), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (bb, bb), 1)
        ).astype(jnp.float32)
        part = jnp.sum(r * eye[:, None, :], axis=2)  # (bb, la)
        part = part * alive.astype(jnp.float32)  # dead lanes contribute 0
        out_ref[...] += part
        sup_ref[...] += part.sum(axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("la_block", "ly_block", "batch_block", "interpret")
)
def nlist_intersect_pallas_es(
    a_pre: jnp.ndarray,
    a_post: jnp.ndarray,
    a_cnt: jnp.ndarray,
    y_pre: jnp.ndarray,
    y_post: jnp.ndarray,
    y_cnt: jnp.ndarray,
    min_count,
    *,
    la_block: int = 512,
    ly_block: int = 512,
    batch_block: int = 8,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Masked early-stop launch: same contract as ``nlist_intersect_pallas``
    plus ``a_cnt`` (A's original node counts, for the bound masses) and a
    dynamic ``min_count`` threshold. Candidates whose final support reaches
    ``min_count`` return exactly the exact kernel's values; provably-doomed
    candidates may return partial merged rows (exact through the tile where
    they died, zero after) and a frozen partial support — always strictly
    below ``min_count``, so thresholding downstream is unaffected.
    ``min_count <= 0`` disables masking and is bit-identical to the exact
    kernel. ``ref.nlist_intersect_masked_ref`` models these semantics."""
    B, La = a_pre.shape
    _, Ly = y_pre.shape
    bb = max(1, min(batch_block, B))
    lab = min(la_block, La)
    lyb = min(ly_block, Ly)
    Bp = (B + bb - 1) // bb * bb
    Lap = (La + lab - 1) // lab * lab
    Lyp = (Ly + lyb - 1) // lyb * lyb
    pad_a = ((0, Bp - B), (0, Lap - La))
    pad_y = ((0, Bp - B), (0, Lyp - Ly))
    a_pre = jnp.pad(a_pre, pad_a, constant_values=jnp.iinfo(jnp.int32).max)
    a_post = jnp.pad(a_post, pad_a, constant_values=-1)
    a_cnt = jnp.pad(a_cnt, pad_a)  # PAD slots carry zero mass
    y_pre = jnp.pad(y_pre, pad_y, constant_values=jnp.iinfo(jnp.int32).max)
    y_post = jnp.pad(y_post, pad_y, constant_values=-1)
    y_cnt = jnp.pad(y_cnt, pad_y)

    nt = Lap // lab
    mass = a_cnt.astype(jnp.float32).reshape(Bp, nt, lab).sum(axis=2)
    rem = jnp.cumsum(mass[:, ::-1], axis=1)[:, ::-1]  # inclusive suffix (Bp, nt)
    stop = jnp.full((1, 1), min_count, jnp.float32)

    out, sup = pl.pallas_call(
        _intersect_es_kernel,
        grid=(Bp // bb, Lap // lab, Lyp // lyb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((bb, nt), lambda b, i, j: (b, 0)),
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, 1), lambda b, i, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Lap), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(stop, rem, a_pre, a_post, y_pre, y_post, y_cnt)
    return out[:B, :La].astype(jnp.int32), sup[:B, 0].astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("la_block", "ly_block", "batch_block", "interpret")
)
def nlist_intersect_pallas(
    a_pre: jnp.ndarray,
    a_post: jnp.ndarray,
    y_pre: jnp.ndarray,
    y_post: jnp.ndarray,
    y_cnt: jnp.ndarray,
    *,
    la_block: int = 512,
    ly_block: int = 512,
    batch_block: int = 8,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """All inputs (B, La) / (B, Ly) int32; returns ``(merged, supports)``:
    merged counts (B, La) int32 plus their row sums (B,) int32, both from
    the one fused ``pallas_call``.

    Padding convention (nlist.pad_nlist): pre = INT32_MAX, post = -1,
    cnt = 0. Padded A slots never pass ``a_pre < y_pre``; padded Y slots
    carry zero count — no extra masks needed, and the same sentinels pad
    the batch axis up to a ``batch_block`` multiple.

    Accumulation is fp32 (exact < 2^24): callers must keep every possible
    count — bounded by the shard's transaction count — below that.
    """
    B, La = a_pre.shape
    _, Ly = y_pre.shape
    bb = max(1, min(batch_block, B))
    lab = min(la_block, La)
    lyb = min(ly_block, Ly)
    Bp = (B + bb - 1) // bb * bb
    Lap = (La + lab - 1) // lab * lab
    Lyp = (Ly + lyb - 1) // lyb * lyb
    pad_a = ((0, Bp - B), (0, Lap - La))
    pad_y = ((0, Bp - B), (0, Lyp - Ly))
    a_pre = jnp.pad(a_pre, pad_a, constant_values=jnp.iinfo(jnp.int32).max)
    a_post = jnp.pad(a_post, pad_a, constant_values=-1)
    y_pre = jnp.pad(y_pre, pad_y, constant_values=jnp.iinfo(jnp.int32).max)
    y_post = jnp.pad(y_post, pad_y, constant_values=-1)
    y_cnt = jnp.pad(y_cnt, pad_y)

    out, sup = pl.pallas_call(
        _intersect_kernel,
        grid=(Bp // bb, Lap // lab, Lyp // lyb),
        in_specs=[
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
            pl.BlockSpec((bb, lyb), lambda b, i, j: (b, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, lab), lambda b, i, j: (b, i)),
            pl.BlockSpec((bb, 1), lambda b, i, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Lap), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(a_pre, a_post, y_pre, y_post, y_cnt)
    return out[:B, :La].astype(jnp.int32), sup[:B, 0].astype(jnp.int32)
