"""Training loop, checkpoint/restart, fault injection, compression, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data import corpus
from repro.models.common import init_params
from repro.models.registry import build_model
from repro.training.compress import (
    compress_with_feedback,
    init_residuals,
    int8_compress,
    int8_decompress,
    topk_compress,
)
from repro.training.optim import OptConfig
from repro.training.step import TrainConfig, make_train_state, make_train_step
from repro.training.trainer import LoopConfig, Trainer
from repro.fault.failures import FailureInjector, SimulatedFailure, StragglerMonitor


def _tiny_model():
    cfg = get_config("tinyllama_1_1b").reduced()
    return cfg, build_model(cfg)


def _batches(cfg, seq=32, batch=2, seed=0):
    toks = corpus.token_stream(20_000, cfg.vocab_size, seed=seed)

    def gen():
        return corpus.batches(toks, batch, seq, seed=seed)

    return gen


def test_loss_decreases():
    cfg, model = _tiny_model()
    tc = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=5, total_steps=60))
    state = make_train_state(model, jax.random.PRNGKey(0), tc)
    step = jax.jit(make_train_step(model, tc))
    gen = _batches(cfg)()
    losses = []
    for i in range(60):
        state, metrics = step(state, next(gen))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.3, (losses[:5], losses[-5:])


def test_checkpoint_restart_bitexact(tmp_path):
    """Failure mid-run + restart from checkpoint == uninterrupted run."""
    cfg, model = _tiny_model()
    tc = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=30))

    def run(ckpt_dir, injector):
        lc = LoopConfig(total_steps=24, ckpt_every=8, ckpt_dir=str(ckpt_dir), log_every=1)
        tr = Trainer(model, tc, lc, _batches(cfg), failure_injector=injector)
        final = tr.train()
        assert final == 24
        state, _ = tr.ckpt.restore()
        return state

    s_fail = run(tmp_path / "a", FailureInjector(fail_at_steps=(13,)))
    s_ok = run(tmp_path / "b", None)
    for a, b in zip(jax.tree.leaves(s_fail["params"]), jax.tree.leaves(s_ok["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_failure_exhausts_retries(tmp_path):
    cfg, model = _tiny_model()
    tc = TrainConfig()
    lc = LoopConfig(total_steps=10, ckpt_every=100, ckpt_dir=str(tmp_path / "c"), max_restarts=2)
    inj = FailureInjector(fail_prob=1.0)
    tr = Trainer(model, tc, lc, _batches(cfg), failure_injector=inj)
    with pytest.raises(SimulatedFailure):
        tr.train()


def test_straggler_monitor():
    m = StragglerMonitor(threshold=2.0)
    assert not m.record(0, 1.0)
    assert not m.record(1, 1.1)
    assert m.record(2, 5.0)  # straggler
    assert m.flagged == [2]
    assert m.mean < 1.2  # straggler did not contaminate the baseline


def test_int8_compression_unbiased_and_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    deqs = []
    for i in range(50):
        q, s = int8_compress(g, jax.random.PRNGKey(i))
        deqs.append(np.asarray(int8_decompress(q, s)))
    err = np.mean(deqs, axis=0) - np.asarray(g)
    assert np.abs(err).max() < 0.01  # stochastic rounding is unbiased
    assert np.abs(deqs[0] - np.asarray(g)).max() <= float(s) * 1.01  # 1-ulp bound


def test_topk_keeps_largest():
    g = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05])
    out = np.asarray(topk_compress(g, 0.4))
    assert set(np.nonzero(out)[0]) == {1, 3}


def test_error_feedback_accumulates():
    """With feedback, the *sum* of delivered grads tracks the sum of true
    grads (compression error does not accumulate)."""
    rng = np.random.default_rng(1)
    true = [jnp.asarray(rng.normal(size=(64,)), jnp.float32) for _ in range(30)]
    res = init_residuals({"g": true[0]})
    delivered = []
    for i, g in enumerate(true):
        out, res = compress_with_feedback({"g": g}, res, jax.random.PRNGKey(i), "topk", 0.1)
        delivered.append(np.asarray(out["g"]))
    total_err = np.sum(delivered, axis=0) - np.sum([np.asarray(g) for g in true], axis=0)
    # residual bound: |err_total| == |final residual| << sum of grads
    np.testing.assert_allclose(total_err, -np.asarray(res["g"]), rtol=1e-4, atol=1e-4)


def test_compressed_training_converges():
    cfg, model = _tiny_model()
    tc = TrainConfig(opt=OptConfig(lr=2e-3, warmup_steps=5, total_steps=100), compression="int8")
    state = make_train_state(model, jax.random.PRNGKey(0), tc)
    step = jax.jit(make_train_step(model, tc))
    gen = _batches(cfg)()
    losses = []
    for i in range(100):
        state, metrics = step(state, next(gen))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.25, (losses[:5], losses[-5:])


def test_serving_engine_greedy():
    from repro.serving.engine import Engine, Request

    cfg, model = _tiny_model()
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch_size=2, max_seq=64)
    reqs = [Request(np.arange(1, 9, dtype=np.int32), max_new=4) for _ in range(2)]
    out = eng.generate(reqs)
    assert all(len(r.out) == 4 for r in out)
    assert all(0 <= t < cfg.padded_vocab for r in out for t in r.out)


def test_checkpoint_elastic_reshard(tmp_path):
    """Save on one 'mesh', restore with different shardings (elasticity)."""
    from repro.checkpoint.ckpt import CheckpointManager
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh

    cm = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    cm.save(0, state, extra={"note": "t"})
    mesh = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, extra = cm.restore(shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    assert extra["note"] == "t"
