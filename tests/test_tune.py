"""Backend registry + kernel-plan autotuner (repro.mining.tune):
resolution rules, MineSpec validation at the resolve() choke point, plan
persistence (cold search -> kernel_plans.json -> warm zero-trial load),
and shape bucketing."""
import json
import os

import pytest

from repro.mining import MineSpec
from repro.mining.tune import (
    PLANS_FILENAME,
    PLANS_SCHEMA,
    KernelPlan,
    KernelTuner,
    _bucket,
    registered_backends,
    resolve_backend,
    static_plan,
)


# ------------------------------------------------------------- the registry
def test_registry_resolution_on_cpu():
    # conftest pins JAX_PLATFORMS=cpu, so "auto" must take the jnp path and
    # "pallas" must fall back to the interpreter
    assert resolve_backend("auto") == "jnp"
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("pallas") == "pallas-interpret"
    assert resolve_backend("pallas-interpret") == "pallas-interpret"


def test_registry_resolution_per_platform():
    assert resolve_backend("auto", "tpu") == "pallas-tpu"
    assert resolve_backend("auto", "gpu") == "pallas-gpu"
    assert resolve_backend("pallas", "tpu") == "pallas-tpu"
    assert resolve_backend("pallas-tpu", "tpu") == "pallas-tpu"
    assert resolve_backend("jnp", "tpu") == "jnp"


def test_platform_locked_backends_raise_elsewhere():
    with pytest.raises(ValueError, match="not available on platform"):
        resolve_backend("pallas-tpu", "cpu")
    with pytest.raises(ValueError, match="not available on platform"):
        resolve_backend("pallas-gpu", "tpu")


def test_unknown_backend_raises_with_registered_list():
    with pytest.raises(ValueError) as e:
        resolve_backend("cuda")
    for name in registered_backends():
        assert name in str(e.value)


def test_minespec_validates_backend_at_resolve():
    """S2: the resolve() choke point rejects unknown names before any
    device work, naming every registered backend."""
    spec = MineSpec(algorithm="hprepost", min_sup=0.5, backend="no-such-backend")
    with pytest.raises(ValueError, match="registered backends"):
        spec.resolve(10)
    # every registered name passes the same gate
    for name in registered_backends():
        assert MineSpec(min_sup=0.5, backend=name).resolve(10) == 5


# ------------------------------------------------------------------ buckets
def test_bucket_next_pow2_clamped():
    assert _bucket(1, 8, 512) == 8
    assert _bucket(8, 8, 512) == 8
    assert _bucket(9, 8, 512) == 16
    assert _bucket(500, 8, 512) == 512
    assert _bucket(5000, 8, 512) == 512
    assert _bucket(0, 8, 1024) == 8


# -------------------------------------------------------------------- plans
def test_static_plan_resolves_backend():
    plan = static_plan("auto", 128, 256, 4, True, platform="cpu")
    assert plan == KernelPlan("jnp", 128, 256, 4, True, "config")
    assert static_plan("pallas", 64, 64, 2, False, platform="cpu").backend == (
        "pallas-interpret"
    )


def test_tuner_cold_search_then_warm_zero_trials(tmp_path):
    """The tune-smoke contract as a unit test: a cold tuner times a search
    and persists the winner; a fresh tuner on the same dir serves the plan
    with zero trials; an in-memory re-ask is a plan hit either way."""
    d = str(tmp_path)
    t1 = KernelTuner(plan_dir=d)
    p1 = t1.plan_for(backend="jnp", B=8, W=16, early_stop=True)
    assert p1.source == "tuned" and p1.backend == "jnp"
    assert t1.stats["trials"] > 0 and t1.stats["tuned"] == 1
    path = os.path.join(d, PLANS_FILENAME)
    assert os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == PLANS_SCHEMA and len(doc["plans"]) == 1

    # same bucketed shape from memory: a hit, no new search
    p1b = t1.plan_for(backend="jnp", B=8, W=16, early_stop=True)
    assert p1b.source == "cached" and t1.stats["tuned"] == 1

    t2 = KernelTuner(plan_dir=d)
    assert t2.stats["loaded_plans"] == 1
    p2 = t2.plan_for(backend="jnp", B=8, W=16, early_stop=True)
    assert t2.stats["trials"] == 0 and t2.stats["plan_hits"] == 1
    assert (p2.la_block, p2.ly_block, p2.batch_block) == (
        p1.la_block, p1.ly_block, p1.batch_block)
    assert p2.source == "cached"


def test_tuner_tune_false_returns_config_defaults(tmp_path):
    t = KernelTuner(plan_dir=str(tmp_path))
    p = t.plan_for(backend="pallas-interpret", B=4, W=16, early_stop=False,
                   defaults=(64, 32, 2), tune=False)
    assert p == KernelPlan("pallas-interpret", 64, 32, 2, False, "config")
    assert t.stats["trials"] == 0 and not t._plans


def test_tuner_ignores_foreign_schema(tmp_path):
    path = os.path.join(str(tmp_path), PLANS_FILENAME)
    with open(path, "w") as f:
        json.dump({"schema": PLANS_SCHEMA + 1, "plans": {"x": {}}}, f)
    t = KernelTuner(plan_dir=str(tmp_path))
    assert t.stats["loaded_plans"] == 0


def test_tuner_keys_split_by_backend_shape_and_early_stop():
    t = KernelTuner()
    k = t._key("jnp", B=100, W=300, early_stop=True)
    assert k == f"jnp|{t._platform}|es1|W512|B128"
    assert t._key("jnp", 100, 300, False) != k
    assert t._key("pallas-interpret", 100, 300, True) != k
    # same bucket -> same key (the memoization grain)
    assert t._key("jnp", 65, 257, True) == k


@pytest.mark.parametrize("backend", ["pallas-tpu", "pallas-interpret", "jnp"])
def test_tuner_search_space_only_tpu_lowerable_blocks(backend):
    """Every (la, bb) the search would time is a block the TPU lowers:
    bb a multiple of 8 or the whole fixture batch, la a multiple of 128
    or the whole fixture width."""
    t = KernelTuner(platform="tpu")
    for B in (1, 3, 8, 9, 100, 512, 5000):
        for W in (1, 8, 64, 128, 300, 1024, 16384):
            bbk, wb, configs = t.search_space(backend, B, W)
            assert configs
            for la, bb in configs:
                assert bb % 8 == 0 or bb == bbk, (B, W, la, bb)
                assert la % 128 == 0 or la == wb, (B, W, la, bb)
