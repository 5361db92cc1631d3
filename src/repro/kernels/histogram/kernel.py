"""Pallas TPU kernel: item-frequency histogram (the paper's Job-1 map).

Counts how many transactions contain each item over a block of rank/item-
encoded transactions ``(R, L)`` with PAD = -1. TPU adaptation of Hadoop's
word-count: instead of emitting (item, 1) pairs and shuffling, each grid
step compares its VMEM-resident row tile against a tile of bin ids and
reduces on-chip — a pure VPU compare + sum with no scatter (TPUs have no
fast random scatter; the dense compare is the native form).

Grid: (bin_blocks, row_blocks). The output bin tile accumulates across the
row-block dimension, which is the innermost grid axis: a TPU keeps an output
block in VMEM only while consecutive programs revisit it, and never reads it
back from HBM, so an accumulated block must not be left and revisited.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(rows_ref, weights_ref, out_ref, *, bin_block: int):
    bi = pl.program_id(0)
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]  # (rb, L) int32
    w = weights_ref[...]  # (rb, 1) int32
    bins = bi * bin_block + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bin_block), 2)
    # (rb, L, bin_block) one-hot compare; PAD (-1) never equals a bin id
    onehot = (rows[:, :, None] == bins).astype(jnp.int32)
    contrib = (onehot.sum(axis=1) * w).sum(axis=0)  # (bin_block,)
    out_ref[...] += contrib[None, :]


@functools.partial(jax.jit, static_argnames=("n_bins", "row_block", "bin_block", "interpret"))
def histogram_pallas(
    rows: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    n_bins: int,
    row_block: int = 256,
    bin_block: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Weighted transaction-count histogram. rows (R, L) int32, PAD=-1."""
    R, L = rows.shape
    rb = min(row_block, max(R, 1))
    bb = min(bin_block, max(n_bins, 1))
    Rp = (R + rb - 1) // rb * rb
    Bp = (n_bins + bb - 1) // bb * bb
    rows = jnp.pad(rows, ((0, Rp - R), (0, 0)), constant_values=-1)
    weights = jnp.pad(weights.astype(jnp.int32), (0, Rp - R)).reshape(Rp, 1)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, bin_block=bb),
        grid=(Bp // bb, Rp // rb),
        in_specs=[
            pl.BlockSpec((rb, L), lambda bi, ri: (ri, 0)),
            pl.BlockSpec((rb, 1), lambda bi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb), lambda bi, ri: (0, bi)),
        out_shape=jax.ShapeDtypeStruct((1, Bp), jnp.int32),
        interpret=interpret,
    )(rows, weights)
    return out[0, :n_bins]
