"""The check catches a broken timed path: each fault the cells can have,
planted in the program under a CPU rehearsal, makes ``correct`` false."""
import io
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import runner
from chipbench.tests import tinyroot


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(runner, "use_compile_cache", lambda root: "off")


def run_cell(root, name):
    out = io.StringIO()
    rc = runner.main(name, 2**31 + 11, 0.2, False, t_process=time.perf_counter(),
                     require_tpu=False, root=root, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def altered_support(monkeypatch):
    """An answer altered where it is produced: the intersect op adds 1 to
    the first candidate's support of every wave."""
    from repro.core import hprepost

    orig = hprepost.nlist_intersect

    def broken(*args, **kwargs):
        new, sup = orig(*args, **kwargs)
        return new, sup + (jnp.arange(sup.shape[0]) == 0).astype(sup.dtype)

    monkeypatch.setattr(hprepost, "nlist_intersect", broken)


def half_the_rows(monkeypatch):
    """Half of the batch left out: prep sees only the first half of the
    rows, the rest turned to padding."""
    from repro.core import hprepost

    orig = hprepost.HPrepostMiner.prepare

    def broken(self, rows, *args, **kwargs):
        rows = np.array(rows, copy=True)
        rows[(len(rows) + 1) // 2:] = -1
        return orig(self, rows, *args, **kwargs)

    monkeypatch.setattr(hprepost.HPrepostMiner, "prepare", broken)


def stale_cache(monkeypatch):
    """A cache that serves another database's prep: every database gets the
    same fingerprint."""
    from repro.mining import engine

    monkeypatch.setattr(engine.MiningEngine, "_fingerprint",
                        lambda self, rows: ((0,), "int32", "same"))


@pytest.mark.parametrize("name,fault", [
    ("kosarak.oneshot", altered_support),
    ("kosarak.oneshot", half_the_rows),
    ("kosarak.oneshot", stale_cache),
    ("mushroom.sweep", altered_support),
    ("mushroom.sweep", half_the_rows),
    ("mushroom.stream", altered_support),
    ("mushroom.stream", half_the_rows),
], ids=lambda x: getattr(x, "__name__", x))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, name, fault):
    fault(monkeypatch)
    res = run_cell(root, name)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0
