"""Pallas TPU kernel: weighted pair co-occurrence (the paper's F2 scan).

``C[i, j] = Σ_rows w · [i ∈ row] · [j ∈ row]`` over rank-encoded rows.
The paper derives frequent 2-itemsets by walking the PPC-tree; the
co-occurrence Gram matrix computes the identical quantity as ``Xᵀ·diag(w)·X``
on the one-hot row matrix — an MXU-native matmul. The kernel materializes
one-hot tiles in VMEM from the compact ``(rb, L)`` row encoding (HBM traffic
stays O(R·L), not O(R·K)) and contracts them on the MXU.

Grid: (ki, kj, row_blocks); the (ki, kj) output tile accumulates across the
row-block dimension. Counts accumulate in fp32 — exact for row blocks
< 2^24; the wrapper chunks rows to stay within that bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cooc_kernel(rows_ref, w_ref, out_ref, *, k_block: int):
    rblk = pl.program_id(2)

    @pl.when(rblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]  # (rb, L)
    w = w_ref[...].astype(jnp.float32)  # (rb, 1)
    ki = pl.program_id(0)
    kj = pl.program_id(1)
    bins_i = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, (1, 1, k_block), 2)
    bins_j = kj * k_block + jax.lax.broadcasted_iota(jnp.int32, (1, 1, k_block), 2)
    xi = (rows[:, :, None] == bins_i).astype(jnp.float32).sum(axis=1)  # (rb, kb)
    xj = (rows[:, :, None] == bins_j).astype(jnp.float32).sum(axis=1)  # (rb, kb)
    out_ref[...] += jax.lax.dot_general(
        xi * w, xj, (((0,), (0,)), ((), ())),
        # weights above 256 are not exact in one bf16 MXU pass
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("n_items", "row_block", "k_block", "interpret")
)
def cooccur_pallas(
    rows: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    n_items: int,
    row_block: int = 256,
    k_block: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """(K, K) weighted co-occurrence counts (full symmetric, diag = support)."""
    R, L = rows.shape
    rb = min(row_block, max(R, 1))
    kb = min(k_block, max(n_items, 1))
    Rp = (R + rb - 1) // rb * rb
    Kp = (n_items + kb - 1) // kb * kb
    rows = jnp.pad(rows, ((0, Rp - R), (0, 0)), constant_values=-1)
    weights = jnp.pad(weights.astype(jnp.int32), (0, Rp - R)).reshape(Rp, 1)

    out = pl.pallas_call(
        functools.partial(_cooc_kernel, k_block=kb),
        grid=(Kp // kb, Kp // kb, Rp // rb),
        in_specs=[
            pl.BlockSpec((rb, L), lambda ki, kj, ri: (ri, 0)),
            pl.BlockSpec((rb, 1), lambda ki, kj, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((kb, kb), lambda ki, kj, ri: (ki, kj)),
        out_shape=jax.ShapeDtypeStruct((Kp, Kp), jnp.float32),
        interpret=interpret,
    )(rows, weights)
    return out[:n_items, :n_items].astype(jnp.int32)
