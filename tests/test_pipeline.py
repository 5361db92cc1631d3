"""GPipe pipeline parallelism: schedule correctness on fake devices."""
import os
import subprocess
import sys
import textwrap

_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.training.pipeline import gpipe_forward

    mesh = make_mesh((4,), ("pipe",))
    L, D = 8, 16          # 8 layers over 4 stages
    n_micro, mb = 6, 4
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(L, D, D)) / np.sqrt(D), jnp.float32)
    bs = jnp.asarray(rng.normal(size=(L, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.normal(size=(n_micro, mb, D)), jnp.float32)

    def layer(lp, h):
        w, b = lp
        return jnp.tanh(h @ w + b)

    got = jax.jit(lambda p, x: gpipe_forward(layer, p, x, mesh=mesh))((ws, bs), x)

    # sequential reference
    def seq(x):
        h = x
        for i in range(L):
            h = layer((ws[i], bs[i]), h)
        return h
    want = jax.vmap(seq)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    print("PIPELINE_OK")
    """
)


def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True, timeout=560
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout
