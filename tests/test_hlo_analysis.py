"""The HLO static analyzer must count known-FLOP programs exactly
(it is the roofline's measurement instrument)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis


def test_scan_matmul_flops_exact():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    res = hlo_analysis.analyze(comp.as_text())
    expected = 7 * 2 * 128 * 256 * 256
    assert res["flops"] == expected
    # bytes: at least the dot operands+outputs each iteration
    assert res["bytes"] >= 7 * (2 * 128 * 256 + 256 * 256) * 4


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, _):
            def inner(d, _):
                return d @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    res = hlo_analysis.analyze(comp.as_text())
    assert res["flops"] == 5 * 3 * 2 * 64 * 64 * 64


def test_no_collectives_on_single_device():
    f = lambda x: x @ x
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    comp = jax.jit(f).lower(x).compile()
    res = hlo_analysis.analyze(comp.as_text())
    assert res["collective_bytes"] == 0
    assert res["flops"] == 2 * 32 * 32 * 32


def test_roofline_refuses_a_device_without_peaks():
    from benchmarks import roofline
    roof = roofline.ff_dense_roofline(128, 784, 2000,
                                      device_kind="TPU v5 lite")
    assert roof["roof_s"] > 0
    with pytest.raises(KeyError, match="no peak rates"):
        roofline.ff_dense_roofline(128, 784, 2000, device_kind="TPU v9")
