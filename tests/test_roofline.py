"""hlo_cost rollup validated against analytically-known workloads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import rollup
from repro.launch.hlo_analysis import collective_bytes


def test_scan_matmul_flops_exact():
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    pc = rollup(jax.jit(scanned).lower(x, ws).compile().as_text())
    want = 12 * 2 * 256**3
    assert abs(pc.flops / want - 1.0) < 0.02, (pc.flops, want)


def test_nested_scan_multiplies():
    def nested(x, ws):
        def outer(c, wg):
            def inner(c2, w):
                return c2 @ w, None
            return jax.lax.scan(inner, c, wg)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 4, 128, 128), jnp.float32)
    pc = rollup(jax.jit(nested).lower(x, ws).compile().as_text())
    want = 12 * 2 * 128**3
    assert abs(pc.flops / want - 1.0) < 0.05, (pc.flops, want)


def test_collectives_inside_scan_multiplied():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))

    def f(x, ws):
        def inner(x, ws):
            def body(c, w):
                return jax.lax.psum(c @ w, "data"), None
            return jax.lax.scan(body, x, ws)[0]
        return jax.shard_map(inner, mesh=mesh, in_specs=(P(), P()), out_specs=P())(x, ws)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
    pc = rollup(jax.jit(f).lower(x, ws).compile().as_text())
    want_payload = 6 * 128 * 128 * 4
    got = sum(pc.collectives.values())
    assert abs(got / want_payload - 1.0) < 0.02, (got, want_payload)
    assert pc.wire_bytes == pytest.approx(2 * want_payload, rel=0.02)  # ring all-reduce


def test_bytes_slice_fusion_not_whole_operand():
    """Reading a (L, n, n) stacked array via per-step dynamic-slice must cost
    ~L·n², not L·(L·n²)."""
    def scanned(x, ws):
        def body(c, w):
            return c * 0.5 + w, None
        return jax.lax.scan(body, x, ws)[0]

    n, L = 512, 16
    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, n, n), jnp.float32)
    pc = rollup(jax.jit(scanned).lower(x, ws).compile().as_text())
    slice_traffic = L * n * n * 4
    assert pc.hbm_bytes < 8 * slice_traffic, (pc.hbm_bytes, slice_traffic)
    assert pc.hbm_bytes > slice_traffic  # but not under-counted either


def test_collective_bytes_text_parser_agrees():
    """The simple text parser (used for reference) sees the same op types."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))

    def f(x):
        return jax.shard_map(
            lambda x: jax.lax.psum(x, "data"),
            mesh=mesh, in_specs=P("data", None), out_specs=P(),
        )(x)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x).compile().as_text()
    cb = collective_bytes(txt)
    assert cb["all-reduce"] > 0 or cb["all-gather"] > 0
