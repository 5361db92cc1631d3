"""Correctness of the §Perf optimization paths: flash attention VJP,
sharded MoE dispatch, chunked sLSTM."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.models.layers import BIG_POS, _flash, _pick_kv_block


def _exact(q, k, v, q_pos, kv_pos, causal=True):
    hd = q.shape[-1]
    s = jnp.einsum("bqhd,bshd->bqhs", q.astype(jnp.float32), k.astype(jnp.float32)) * hd**-0.5
    mask = kv_pos[:, None, :] <= q_pos[:, :, None] if causal else kv_pos[:, None, :] < BIG_POS
    s = jnp.where(mask[:, :, None, :], s, -1e30)
    return jnp.einsum("bqhs,bshd->bqhd", jax.nn.softmax(s, -1), v.astype(jnp.float32)).astype(q.dtype)


@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 2),
    s=st.sampled_from([16, 48, 64, 96]),
    h=st.integers(1, 3),
    hd=st.sampled_from([8, 16]),
    causal=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_matches_exact_fwd_bwd(b, s, h, hd, causal, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)
    kb = _pick_kv_block(s)
    o1 = _flash(q, k, v, pos, pos, causal, kb)
    o2 = _exact(q, k, v, pos, pos, causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=3e-5, atol=3e-5)
    f = lambda *a: _flash(*a, pos, pos, causal, kb).sum()
    e = lambda *a: _exact(*a, pos, pos, causal).sum()
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(e, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3)


def test_flash_masks_unfilled_cache_slots():
    """kv_pos = BIG_POS (unfilled cache) must contribute nothing."""
    rng = np.random.default_rng(0)
    B, S, H, hd = 1, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 8, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(8)[None], (B, 8)).astype(jnp.int32)
    kv_pos_full = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    kv_pos_half = jnp.where(kv_pos_full < 8, kv_pos_full, BIG_POS)
    o_half = _flash(q, k, v, q_pos, kv_pos_half, True, 8)
    o_trunc = _flash(q, k[:, :8], v[:, :8], q_pos, kv_pos_full[:, :8], True, 8)
    np.testing.assert_allclose(np.asarray(o_half), np.asarray(o_trunc), rtol=1e-5, atol=1e-5)


def test_moe_sharded_equals_dense():
    from repro.configs.base import get_config
    from repro.models.common import init_params
    from repro.models.moe import _moe_dense, moe_ffn, moe_specs
    from repro.launch.mesh import make_mesh

    cfg = dataclasses.replace(get_config("granite_moe").reduced(), capacity_factor=4.0)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
    out_d, aux_d = jax.jit(lambda p, x: _moe_dense(p, x, cfg))(p, x)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        out_s, aux_s = jax.jit(lambda p, x: moe_ffn(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_s), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_d), float(aux_s), rtol=1e-5)


@pytest.mark.parametrize("S", [1, 16, 64, 96, 128])
def test_slstm_chunking_matches_flat(S):
    """Chunked/unrolled sLSTM must equal a flat per-step recurrence."""
    from repro.configs.base import get_config
    from repro.models.common import init_params
    from repro.models.ssm import slstm, slstm_specs

    cfg = get_config("xlstm_125m").reduced()
    p = init_params(slstm_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model), jnp.float32)
    y, st1 = jax.jit(lambda p, x: slstm(p, x, cfg))(p, x)
    # flat reference: feed one token at a time through the single-step path
    state = None
    outs = []
    for t in range(S):
        yt, state = slstm(p, x[:, t : t + 1], cfg, state=state, single_step=True)
        outs.append(yt)
    y2 = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y2, np.float32), rtol=2e-4, atol=2e-4
    )


def test_corpus_phrases_recovered():
    """Injected n-gram phrases come back as high-support itemsets."""
    from repro.core.prepost import mine_prepost
    from repro.data import corpus

    toks = corpus.token_stream(30_000, 256, seed=3, n_phrases=4, phrase_len=3, phrase_rate=0.25)
    rows = corpus.ngram_transactions(toks, window=6, stride=3)
    res = mine_prepost(rows, 256, int(0.03 * len(rows)), max_k=3)
    three = [k for k in res.itemsets if len(k) == 3]
    assert len(three) >= 3  # the injected phrases (as sets) are frequent


def test_prefetcher_overlap_and_skip():
    import itertools
    from repro.data.pipeline import Prefetcher

    gen = ({"i": np.asarray(i)} for i in itertools.count())
    pf = Prefetcher(gen, depth=4)
    first = pf.next()["i"]
    pf.skip_slow(2)
    later = pf.next()["i"]
    assert later > first
    assert pf.skipped == 2
    pf.close()
