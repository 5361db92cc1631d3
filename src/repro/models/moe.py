"""Mixture-of-Experts FFN: top-k routing with expert parallelism.

TPU-native dispatch: tokens are routed by a stable sort on expert id
(gather), processed by the expert-sharded FFN batch, and combined by a
scatter-add — O(T·k·d) data movement instead of the O(T·E·C·d) one-hot
dispatch einsum of GShard. Capacity-bounded (tokens over capacity are
dropped, standard for capacity-factor routers); an auxiliary load-balance
loss (Switch-style) is returned alongside.

Two dispatch paths:

  - ``moe_ffn``        — plain jit/GSPMD path (single device, smoke tests).
  - ``_moe_sharded``   — shard_map path, chosen automatically when an
    ambient mesh with a ``model`` axis is set. Routing is computed
    *replicated* per data shard (deterministic, no comms); each model shard
    gathers only its own experts' capacity buffers locally and the combine
    ends in one ``psum`` over ``model`` — the same single all-reduce a
    row-parallel dense MLP pays. This replaced a global argsort dispatch
    whose cross-device sort made granite_moe train 238 s collective-bound
    (EXPERIMENTS.md §Perf hillclimb #2: 238 s -> ~0.1 s collective term).

Experts shard over ``model`` (phi3.5: 16e/16-way = 1 expert per shard;
granite: 32e = 2 per shard).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ParamSpec


def moe_specs(cfg) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((d, E), ("embed", None)),
        "wi": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "wg": ParamSpec((E, d, f), ("experts", "embed", "ff")),
        "wo": ParamSpec((E, f, d), ("experts", "ff", "embed")),
    }


def _ambient_moe_axes(cfg, batch: int):
    """(data_axes, model_axis) if the ambient mesh supports sharded dispatch."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or getattr(am, "empty", True):
        return None
    names = getattr(am, "axis_names", ())
    if "model" not in names:
        return None
    M = am.shape["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    D = 1
    for a in data_axes:
        D *= am.shape[a]
    if cfg.n_experts % M or batch % max(D, 1):
        return None
    return data_axes, "model", D, M


def moe_ffn(p: dict, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar). Dispatches to the
    shard_map path when an ambient (data, model) mesh is active."""
    ax = _ambient_moe_axes(cfg, x.shape[0])
    if ax is not None:
        return _moe_sharded(p, x, cfg, *ax)
    return _moe_dense(p, x, cfg)


def _moe_sharded(p, x, cfg, data_axes, model_ax, D, M):
    E, k = cfg.n_experts, cfg.experts_per_token
    e_per = E // M
    B, S, d = x.shape
    T_l = (B // max(D, 1)) * S
    cap = max(1, int(cfg.capacity_factor * T_l * k / E))

    def body(xb, router, wi, wg, wo):
        # xb (B_l, S, d); router (d, E) replicated; wi/wg/wo (E/M, ...) local
        B_l = xb.shape[0]
        xt = xb.reshape(B_l * S, d)
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros(E, jnp.float32).at[eidx.reshape(-1)].add(1.0) / (B_l * S * k)
        aux = (me * ce).sum() * E
        aux = jax.lax.pmean(aux, data_axes) if data_axes else aux

        # keep only this model shard's experts, then local sort-dispatch.
        # All O(T·k) work stays on int32/f32 *index* arrays; the d-wide
        # tensors are touched only at slot granularity (E/M × cap rows) —
        # §Perf iteration 2: per-assignment-width buffers were 12.8× larger.
        my0 = jax.lax.axis_index(model_ax) * e_per
        flat_e = eidx.reshape(-1)
        flat_gate = gate.reshape(-1)
        src = jnp.repeat(jnp.arange(B_l * S), k)
        mine = (flat_e >= my0) & (flat_e < my0 + e_per)
        local_e = jnp.where(mine, flat_e - my0, e_per)  # foreign -> trash expert
        order = jnp.argsort(local_e, stable=True)
        e_sorted = local_e[order]
        starts = jnp.searchsorted(e_sorted, jnp.arange(e_per + 1))
        pos = jnp.arange(e_sorted.shape[0]) - starts[jnp.clip(e_sorted, 0, e_per)]
        keep = (e_sorted < e_per) & (pos < cap)
        slot = jnp.where(keep, e_sorted * cap + pos, e_per * cap)

        ns = e_per * cap
        tok_for_slot = jnp.zeros(ns + 1, jnp.int32).at[slot].set(src[order].astype(jnp.int32))
        gate_for_slot = (
            jnp.zeros(ns + 1, jnp.float32).at[slot].set(jnp.where(keep, flat_gate[order], 0.0))
        )[:ns]
        xin = xt[tok_for_slot[:ns]].reshape(e_per, cap, d)  # slot-granular gather

        def expert(we_i, we_g, we_o, h):
            a = jax.nn.silu(h @ we_g.astype(h.dtype)) * (h @ we_i.astype(h.dtype))
            return a @ we_o.astype(h.dtype)

        hout = jax.vmap(expert)(wi, wg, wo, xin)  # (E/M, cap, d)
        contrib = hout.reshape(ns, d) * gate_for_slot[:, None].astype(xb.dtype)
        out = jnp.zeros((B_l * S, d), xb.dtype).at[tok_for_slot[:ns]].add(contrib)
        out = jax.lax.psum(out, model_ax)  # merge expert shards (row-parallel)
        return out.reshape(B_l, S, d), aux

    dspec = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    out, aux = jax.shard_map(
        body,
        in_specs=(P(dspec, None, None), P(), P("model"), P("model"), P("model")),
        out_specs=(P(dspec, None, None), P()),
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return out, aux


def _moe_dense(p: dict, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: fraction of tokens per expert × mean router prob
    me = probs.mean(axis=0)
    ce = jnp.zeros(E, jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * k)
    aux = (me * ce).sum() * E

    cap = int(cfg.capacity_factor * T * k / E)
    cap = max(cap, 1)

    flat_e = eidx.reshape(-1)  # (T*k,)
    flat_gate = gate.reshape(-1)
    src = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_e, stable=True)  # group by expert
    e_sorted = flat_e[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E))
    pos = jnp.arange(T * k) - starts[e_sorted]  # slot within expert
    keep = pos < cap
    slot = jnp.where(keep, e_sorted * cap + pos, E * cap)  # overflow -> trash row

    xin = jnp.zeros((E * cap + 1, d), x.dtype).at[slot].set(xt[src[order]])
    xin = xin[: E * cap].reshape(E, cap, d)

    def expert(we_i, we_g, we_o, h):
        a = jax.nn.silu(h @ we_g.astype(h.dtype)) * (h @ we_i.astype(h.dtype))
        return a @ we_o.astype(h.dtype)

    hout = jax.vmap(expert)(p["wi"], p["wg"], p["wo"], xin)  # (E, cap, d)
    hflat = jnp.concatenate([hout.reshape(E * cap, d), jnp.zeros((1, d), x.dtype)])

    contrib = hflat[slot] * flat_gate[order][:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[src[order]].add(jnp.where(keep[:, None], contrib, 0))
    return out.reshape(B, S, d), aux
