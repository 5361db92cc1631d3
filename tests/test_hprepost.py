"""Distributed HPrepost vs single-shard PrePost.

In-process tests use a 1-device mesh; true multi-device behaviour (psum
across DB blocks, candidate partitioning over `model`, the shuffle) runs in
a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 since
device count is locked at first JAX init.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.encoding import pad_transactions
from repro.core.hprepost import HPrepostConfig, HPrepostMiner
from repro.core.prepost import mine_prepost
from repro.data.synth import random_db


@pytest.fixture(scope="module")
def mesh11():
    import jax
    from repro.launch.mesh import make_mesh

    return make_mesh((1, 1), ("data", "model"))


def test_paper_example_distributed(mesh11, paper_db):
    rows, n_items = paper_db
    miner = HPrepostMiner(mesh11, config=HPrepostConfig(candidate_unit=4))
    res = miner.mine(rows, n_items, 3)
    ref = mine_prepost(rows, n_items, 3)
    assert res.itemsets == ref.itemsets


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("min_count", [1, 3])
def test_random_matches_single_shard(mesh11, seed, min_count):
    rng = np.random.default_rng(seed)
    rows = random_db(rng, 80, 12, 7)
    miner = HPrepostMiner(mesh11, config=HPrepostConfig(candidate_unit=8))
    res = miner.mine(rows, 12, min_count)
    ref = mine_prepost(rows, 12, min_count)
    assert res.itemsets == ref.itemsets


def test_mode_a_no_model_axis(mesh11, paper_db):
    rows, n_items = paper_db
    miner = HPrepostMiner(
        mesh11, model_axis=None, config=HPrepostConfig(candidate_unit=4, partition_candidates=False)
    )
    res = miner.mine(rows, n_items, 2)
    ref = mine_prepost(rows, n_items, 2)
    assert res.itemsets == ref.itemsets


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.launch.mesh import make_mesh
    from repro.core.hprepost import HPrepostMiner, HPrepostConfig
    from repro.core.prepost import mine_prepost
    from repro.data.synth import random_db

    mesh = make_mesh((4, 2), ("data", "model"))
    for seed in range(4):
        rng = np.random.default_rng(seed)
        rows = random_db(rng, 100, 12, 6)
        for mode_b in (True, False):
            miner = HPrepostMiner(
                mesh,
                config=HPrepostConfig(candidate_unit=8, partition_candidates=mode_b),
            )
            res = miner.mine(rows, 12, 2)
            ref = mine_prepost(rows, 12, 2)
            assert res.itemsets == ref.itemsets, (seed, mode_b)

    # multi-pod style: data over two axes
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rng = np.random.default_rng(7)
    rows = random_db(rng, 64, 10, 5)
    miner = HPrepostMiner(mesh3, data_axis=("pod", "data"), config=HPrepostConfig(candidate_unit=8))
    res = miner.mine(rows, 10, 2)
    ref = mine_prepost(rows, 10, 2)
    assert res.itemsets == ref.itemsets
    print("MULTIDEV_OK")
    """
)


def test_multidevice_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MULTIDEV_OK" in out.stdout
