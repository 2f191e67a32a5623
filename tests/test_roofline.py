"""Loop-aware HLO cost model: trip-count handling, dot FLOPs, collectives."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_cost import HloCost, _wire_bytes


def test_scan_trip_count_multiplier():
    def g(x):
        w0 = jnp.eye(128)

        def body(c, _):
            return c @ w0, None

        y, _ = jax.lax.scan(body, x, None, length=12)
        return y.sum()

    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    text = jax.jit(g).lower(xs).compile().as_text()
    got = HloCost(text, 1).total().flops
    expect = 12 * 2 * 128 ** 3
    # XLA's own analysis counts the body ONCE; ours must count 12
    raw = jax.jit(g).lower(xs).compile().cost_analysis()
    if isinstance(raw, list):  # older jax returned [dict]
        raw = raw[0]
    raw = raw["flops"]
    assert raw < expect / 6
    assert abs(got - expect) / expect < 0.05


def test_nested_scan_multiplies():
    def g(x):
        w0 = jnp.eye(64)

        def inner(c, _):
            return c @ w0, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None

        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y.sum()

    xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    text = jax.jit(g).lower(xs).compile().as_text()
    got = HloCost(text, 1).total().flops
    expect = 20 * 2 * 64 ** 3
    assert abs(got - expect) / expect < 0.05


def test_dot_flops_exact():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    text = jax.jit(f).lower(a, b).compile().as_text()
    got = HloCost(text, 1).total().flops
    assert abs(got - 2 * 64 * 256 * 32) / (2 * 64 * 256 * 32) < 0.05


def test_wire_byte_ring_model():
    assert _wire_bytes("all-gather", 1000, 4) == 750
    assert _wire_bytes("all-reduce", 1000, 4) == 1500
    assert _wire_bytes("reduce-scatter", 1000, 4) == 3000
    assert _wire_bytes("all-to-all", 1000, 4) == 750
    assert _wire_bytes("collective-permute", 1000, 4) == 1000
    assert _wire_bytes("all-reduce", 1000, 1) == 0


def test_collectives_parsed_from_sharded_module():
    pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run via subprocess suite)")
