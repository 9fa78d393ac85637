"""HLO cost walker: exact on loop-free graphs, trip-count-correct on scans."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_loopfree_matches_xla_cost_analysis():
    def f(x, w):
        return jnp.tanh(x @ w) @ w

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, x, x)
    got = analyze(c.as_text())
    xla = c.cost_analysis()
    assert got.flops == pytest.approx(xla["flops"], rel=0.01)


def test_scan_flops_scale_with_trip_count():
    def f(x, w):
        def body(c, wl):
            return c @ wl, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    per_layer = 2 * 64**3
    for L in (1, 4, 16):
        w = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
        got = analyze(_compile(f, x, w).as_text())
        assert got.flops == pytest.approx(L * per_layer, rel=0.02), L


def test_nested_scan_trips_multiply():
    def f(x, w):
        def outer(c, wl):
            def inner(ci, _):
                return ci @ wl, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, w)
        return out

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    got = analyze(_compile(f, x, w).as_text())
    assert got.flops == pytest.approx(5 * 3 * 2 * 32**3, rel=0.05)


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    got = analyze(_compile(f, a, b).as_text())
    assert got.flops == pytest.approx(2 * 4 * 32 * 16 * 8, rel=0.01)


def test_bytes_reasonable_for_copy_free_graph():
    def f(x):
        return x * 2.0 + 1.0

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    got = analyze(_compile(f, x).as_text())
    # in+out = 8 MB; allow generous slack for fusion accounting
    assert 4e6 <= got.bytes <= 2.5e7
