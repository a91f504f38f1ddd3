"""The loop-aware HLO cost parser vs ground truth (unrolled modules)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_match_unrolled():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, w):
        return jax.lax.scan(body, x, w)[0]

    def unrolled(x, w):
        for i in range(8):
            x, _ = body(x, w[i])
        return x

    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    fs = hlo_cost.analyze(_hlo(scanned, x, w)).flops
    fu = hlo_cost.analyze(_hlo(unrolled, x, w)).flops
    expected = 2 * 4 * 64 * 64 * 8
    assert fs == expected
    assert fu == expected


def test_nested_scan():
    def nested(x, w):
        def outer(c, wo):
            def inner(cc, wi):
                return jnp.tanh(cc @ wi), None
            return jax.lax.scan(inner, c, wo)[0], None
        return jax.lax.scan(outer, x, w)[0]

    x = jax.ShapeDtypeStruct((4, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 5, 32, 32), jnp.float32)
    f = hlo_cost.analyze(_hlo(nested, x, w)).flops
    assert f == 2 * 4 * 32 * 32 * 15


def test_cost_analysis_undercounts_loops():
    """The reason this module exists: XLA's own analysis counts the body
    once.  If this ever starts failing, cost_analysis got fixed upstream and
    the parser can be retired."""
    def body(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, w):
        return jax.lax.scan(body, x, w)[0]

    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    compiled = jax.jit(scanned).lower(x, w).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):        # older jax: list of per-device dicts
        ca = ca[0] if ca else {}
    xla_flops = ca.get("flops", 0)
    ours = hlo_cost.analyze(compiled.as_text()).flops
    assert ours >= 7 * xla_flops


def test_dynamic_loop_uses_hint():
    def dyn(x, w, n):
        def body(i, c):
            return jnp.tanh(c @ w)
        return jax.lax.fori_loop(0, n, body, x)

    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    hlo = _hlo(dyn, x, w, n)
    c1 = hlo_cost.analyze(hlo, dynamic_trip_hint=1.0)
    c10 = hlo_cost.analyze(hlo, dynamic_trip_hint=10.0)
    assert c1.dynamic_loops >= 1
    assert c10.flops == pytest.approx(10 * c1.flops, rel=1e-6)


def test_collectives_counted():
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run under XLA_FLAGS host platform)")
    mesh = make_mesh((len(jax.devices()),), ("d",))

    def f(x):
        return jax.lax.psum(x, "d")

    sf = jax.jit(shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P()))
    hlo = sf.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)) \
            .compile().as_text()
    c = hlo_cost.analyze(hlo)
    assert c.as_dict()["collectives"]["all-reduce"]["count"] >= 1


def test_shape_bytes():
    assert hlo_cost._shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert hlo_cost._shape_bytes("bf16[4]") == 8
    assert hlo_cost._shape_bytes("(f32[2,2], s32[3])") == 16 + 12
    assert hlo_cost._shape_bytes("pred[7]") == 7


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch import roofline
    cost = {"flops": 197e12, "bytes accessed": 819e9 / 2}
    out = roofline.roofline(cost, {"total_bytes": 0}, 1,
                            device_kind="TPU v5 lite")
    assert out["compute_s"] == pytest.approx(1.0)
    assert out["memory_s"] == pytest.approx(0.5)
    assert out["bottleneck"] == "compute"
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.roofline(cost, {"total_bytes": 0}, 1, device_kind="cpu")
