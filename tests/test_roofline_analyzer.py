"""Validates the trip-weighted HLO analyzer against XLA's own cost_analysis
(exact on loop-free programs) and against unrolled-vs-scanned equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.launch.roofline import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_dot_flops_match_cost_analysis():
    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 64), jnp.float32)
    c = _compile(lambda a, b: a @ b, a, b)
    ana = analyze_hlo(c.as_text())
    expect = 2 * 128 * 256 * 64
    assert abs(ana.flops - expect) / expect < 0.05, (ana.flops, expect)
    ca = c.cost_analysis()
    if ca and ca.get("flops"):
        assert abs(ana.flops - ca["flops"]) / ca["flops"] < 0.1


def test_chained_dots():
    a = jnp.zeros((64, 64), jnp.float32)

    def f(a):
        for _ in range(4):
            a = jnp.tanh(a @ a)
        return a

    c = _compile(f, a)
    ana = analyze_hlo(c.as_text())
    expect = 4 * 2 * 64 ** 3
    assert abs(ana.flops - expect) / expect < 0.1, (ana.flops, expect)


def test_scan_flops_are_trip_weighted():
    """A scanned matmul must count trips x body flops (cost_analysis gets
    this wrong; our analyzer must not)."""
    w = jnp.zeros((16, 64, 64), jnp.float32)
    x = jnp.zeros((8, 64), jnp.float32)

    def scanned(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    def unrolled(w, x):
        h = x
        for i in range(16):
            h = jnp.tanh(h @ w[i])
        return h

    c_s = _compile(scanned, w, x)
    c_u = _compile(unrolled, w, x)
    f_s = analyze_hlo(c_s.as_text()).flops
    f_u = analyze_hlo(c_u.as_text()).flops
    expect = 16 * 2 * 8 * 64 * 64
    assert abs(f_u - expect) / expect < 0.1, (f_u, expect)
    assert abs(f_s - expect) / expect < 0.15, (f_s, expect)


def test_nested_scan_weighting():
    w = jnp.zeros((4, 64, 64), jnp.float32)
    x = jnp.zeros((8, 64), jnp.float32)

    def f(w, x):
        def outer(h, _):
            def inner(h2, wl):
                return jnp.tanh(h2 @ wl), None
            h, _ = jax.lax.scan(inner, h, w)
            return h, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h

    c = _compile(f, w, x)
    ana = analyze_hlo(c.as_text())
    expect = 3 * 4 * 2 * 8 * 64 * 64
    assert abs(ana.flops - expect) / expect < 0.2, (ana.flops, expect)


def test_collective_bytes_counted():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh((2,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(a):
        b = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P("x", None)))
        return jnp.sum(b * 2.0)          # all-reduce at the end

    a = jax.ShapeDtypeStruct((128, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "x")))
    with jax.set_mesh(mesh):
        c = jax.jit(f).lower(a).compile()
    ana = analyze_hlo(c.as_text())
    assert ana.collective_bytes > 0
    assert sum(ana.count_by_kind.values()) >= 1


def test_hbm_bytes_reasonable():
    a = jnp.zeros((512, 512), jnp.float32)
    c = _compile(lambda a: a @ a, a)
    ana = analyze_hlo(c.as_text())
    lo = 3 * 512 * 512 * 4               # read a twice + write out
    assert ana.hbm_bytes >= lo * 0.5
    assert ana.hbm_bytes <= lo * 20
