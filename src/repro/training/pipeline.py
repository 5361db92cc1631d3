"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Each pipeline stage owns a contiguous slice of layers (params sharded over
``pipe`` on the stacked-layer axis). A microbatched forward runs the classic
GPipe schedule: at tick t, stage s processes microbatch t-s; activations move
stage-to-stage with ``jax.lax.ppermute`` (the point-to-point hop the TPU ICI
torus serves directly). ``n_micro >= n_stages`` microbatches keep the bubble
at the standard (S-1)/(M+S-1) fraction.

This composes with the DP/TP sharding of everything *inside* a stage — the
multi-pod dry-run uses DP×TP(+pod) as the primary layout, and this module is
the PP alternative exercised on host meshes (tests/test_pipeline.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def gpipe_forward(
    layer_fn,
    stacked_params,
    x: jnp.ndarray,  # (n_micro, micro_batch, ...) microbatched input
    *,
    mesh,
    axis: str = "pipe",
):
    """Run ``layer_fn(params_slice, h)`` through S pipeline stages.

    ``stacked_params``: pytree with leading (n_layers,) axes, n_layers % S == 0;
    stage s owns layers [s·L/S, (s+1)·L/S). Returns (n_micro, micro_batch, ...)
    outputs. Implemented as a shard_map over ``axis`` with a ppermute ring.
    """
    S = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro % 1 == 0 and n_micro >= S, (n_micro, S)

    def stage_body(params_local, xs_local):
        # params_local: leaves with leading (L/S,) — this stage's layers
        # xs_local: (n_micro, micro, ...) full microbatch queue (replicated)
        sid = jax.lax.axis_index(axis)

        def run_stage(h):
            def body(c, lp):
                return layer_fn(lp, c), None
            out, _ = jax.lax.scan(body, h, params_local)
            return out

        n_ticks = n_micro + S - 1
        # initial carries must already be device-varying for the scan
        buf = jax.lax.pcast(jnp.zeros_like(xs_local[0]), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs_local), (axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t from the queue; others use the
            # activation that arrived from the previous stage
            mb = jnp.clip(t, 0, n_micro - 1)
            h_in = jnp.where(sid == 0, xs_local[mb], buf)
            h_out = run_stage(h_in)
            # last stage emits microbatch t - (S-1) (branch-free select:
            # lax.cond branches would disagree on varying-manual-axes types)
            emit = t - (S - 1)
            valid_emit = (emit >= 0) & (emit < n_micro) & (sid == S - 1)
            upd = jax.lax.dynamic_update_slice(
                outs, h_out[None].astype(outs.dtype),
                (jnp.clip(emit, 0, n_micro - 1),) + (0,) * (outs.ndim - 1),
            )
            outs = jnp.where(valid_emit, upd, outs)
            # hand the activation to the next stage (ring permute)
            nxt = jax.lax.ppermute(h_out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        outs = jax.lax.psum(jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    return jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )(stacked_params, x)
