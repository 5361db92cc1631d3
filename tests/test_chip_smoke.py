"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases' control flow and checks hold at a tiny scale on the CPU (the jnp
path, and the Pallas interpreter for the kernel phase)."""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
TINY = {"kosarak": 0.001, "mushroom": 0.02}


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU found" in out.stdout


@pytest.mark.parametrize("phase", ["kernel", "serve", "stream"])
def test_phase_at_tiny_scale(smoke, phase):
    if phase == "kernel":
        smoke.kernel_phase(scale=TINY, interpret=True)
    elif phase == "serve":
        smoke.serve_phase(scale=TINY, backend="jnp", plan_backend="jnp",
                          need_stop=False)
    else:
        smoke.stream_phase(scale=TINY, backend="jnp", plan_backend="jnp")


def test_partitioned_phase_on_four_cpu_devices():
    """The ``--chips 4`` path on 4x1 and 2x2 meshes of fake CPU devices
    (a fresh process: the device count is fixed at JAX's start)."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        f"chip_smoke.partitioned_phase(scale={TINY!r}, backend='jnp')"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code, str(REPO)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count("4 distinct data shards") == 2
    assert out.stdout.count("2 distinct data shards") == 2
