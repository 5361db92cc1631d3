"""Compile the mining path's Pallas kernels for a described (not attached)
TPU v5e chip at the widths a real run uses.

Interpret mode cannot see what the TPU's compiler refuses: block shapes off
the (8, 128) tiling, scoped-VMEM overruns, ops Mosaic cannot lower. The
compiler is installed without the chip, so these tests catch such refusals
on any machine that can describe the topology. Nothing here runs a kernel.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test runner's workers import every test file.
"""
import os

import pytest

# (W, la, ly, bb): the wave kernels at their default blocks; W=2048 is the
# mushroom width at min_sup=0.12, W=16384 the kosarak width at min_sup=0.01
WAVE_WIDTHS = (2048, 16384)
WAVE_BATCH = 64
# kosarak: 990,002 rows x 48 slots over 41,270 items (Job 1 histogram)
KOSARAK_ROWS, KOSARAK_L, KOSARAK_ITEMS = 990_002, 48, 41_270
# mushroom: 8,124 rows x 23 slots, |F1|=68 at min_sup=0.12 (F2 co-occurrence)
MUSHROOM_ROWS, MUSHROOM_L, MUSHROOM_F1 = 8_124, 23, 68


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """-> compile(fn, *shapes): lower and compile ``fn`` for one described
    v5e chip, with the persistent compile cache off (an entry compiled for
    a described chip cannot be read back without one)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("W", WAVE_WIDTHS)
def test_wave_kernel_exact_compiles(compile_for_chip, W):
    import jax.numpy as jnp

    from repro.kernels.nlist_intersect.kernel import nlist_intersect_pallas

    compiled = compile_for_chip(
        lambda *a: nlist_intersect_pallas(*a, la_block=512, ly_block=512, batch_block=8),
        *[((WAVE_BATCH, W), jnp.int32)] * 5,
    )
    assert _is_kernel(compiled)


@pytest.mark.parametrize("W", WAVE_WIDTHS)
def test_wave_kernel_early_stop_compiles(compile_for_chip, W):
    import jax.numpy as jnp

    from repro.kernels.nlist_intersect.kernel import nlist_intersect_pallas_es

    compiled = compile_for_chip(
        lambda *a: nlist_intersect_pallas_es(
            *a, la_block=512, ly_block=512, batch_block=8
        ),
        *[((WAVE_BATCH, W), jnp.int32)] * 6,
        ((), jnp.int32),
    )
    assert _is_kernel(compiled)


def test_histogram_compiles_at_kosarak_shape(compile_for_chip):
    import jax.numpy as jnp

    from repro.kernels.histogram.kernel import histogram_pallas

    compiled = compile_for_chip(
        lambda r, w: histogram_pallas(r, w, n_bins=KOSARAK_ITEMS),
        ((KOSARAK_ROWS, KOSARAK_L), jnp.int32),
        ((KOSARAK_ROWS,), jnp.int32),
    )
    assert _is_kernel(compiled)


def test_cooccur_compiles_at_mushroom_shape(compile_for_chip):
    import jax.numpy as jnp

    from repro.kernels.cooccur.kernel import cooccur_pallas

    compiled = compile_for_chip(
        lambda r, w: cooccur_pallas(r, w, n_items=MUSHROOM_F1),
        ((MUSHROOM_ROWS, MUSHROOM_L), jnp.int32),
        ((MUSHROOM_ROWS,), jnp.int32),
    )
    assert _is_kernel(compiled)
