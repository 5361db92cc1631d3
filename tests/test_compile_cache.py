"""The persistent compilation cache goes where ``use_compile_cache`` says:
``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed ``<checkout>/.jax_cache``.
Each case runs in a fresh process, since JAX fixes the cache directory at
its first compile."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_CHILD = textwrap.dedent(
    """
    import json
    from repro.launch.compile_cache import use_compile_cache

    first, second = use_compile_cache(), use_compile_cache()
    import jax, jax.numpy as jnp

    def compile_cache_probe(x):
        return jnp.cos(x) * 3 + 1

    jax.block_until_ready(jax.jit(compile_cache_probe)(jnp.ones(8)))
    print(json.dumps({"first": first, "second": second,
                      "config": jax.config.jax_compilation_cache_dir}))
    """
)


def _run(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _probe_entries(d: pathlib.Path):
    return [p for p in d.iterdir() if p.name.startswith("jit_compile_cache_probe-")]


@pytest.mark.parametrize("use_env", [True, False], ids=["env_dir", "checkout_dir"])
def test_compile_cache_directory(tmp_path, use_env):
    want = tmp_path / "cache" if use_env else REPO / ".jax_cache"
    got = _run(want if use_env else None)
    assert got["first"] == got["second"] == got["config"] == str(want)
    assert _probe_entries(want), sorted(p.name for p in want.iterdir())
    if not use_env:  # the fixed path is the same in the next process too
        assert _run(None)["config"] == str(want)
