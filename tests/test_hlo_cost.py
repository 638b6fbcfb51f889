"""Trip-count-aware HLO cost walker: exactness on known programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import module_cost, parse_module, shape_bytes


def _cost(fn, *specs):
    return module_cost(jax.jit(fn).lower(*specs).compile().as_text())


def test_single_matmul_exact():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _cost(lambda a, b: a @ b, x, x)
    assert c["flops"] == 2 * 128 ** 3


def test_scan_multiplies_trip_count():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(a, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), a, w)[0]

    c = _cost(scanned, x, ws)
    assert c["flops"] == pytest.approx(10 * 2 * 128 ** 3, rel=0.01)


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 4, 64, 64), jnp.float32)

    def nested(a, w):
        def outer(c, wo):
            def inner(ci, wi):
                return ci @ wi, None
            return jax.lax.scan(inner, c, wo)[0], None
        return jax.lax.scan(outer, a, w)[0]

    c = _cost(nested, x, ws)
    assert c["flops"] == pytest.approx(12 * 2 * 64 ** 3, rel=0.01)


def test_xla_cost_analysis_undercounts_loops():
    """Documents WHY the walker exists: XLA counts loop bodies once."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(a, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), a, w)[0]

    ca = jax.jit(scanned).lower(x, ws).compile().cost_analysis()
    walker = _cost(scanned, x, ws)["flops"]
    assert walker > 5 * ca["flops"]


def test_bytes_reasonable_for_copy():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = _cost(lambda a: a * 2.0, x)
    nbytes = 1024 * 1024 * 4
    # one read + one write, modulo minor bookkeeping
    assert nbytes <= c["bytes"] <= 4 * nbytes


def test_shape_bytes_tuple_and_layout():
    assert shape_bytes("f32[4,4]{1,0}") == 64
    assert shape_bytes("(f32[2], bf16[3,3]{1,0})") == 8 + 18
    assert shape_bytes("pred[]") == 1


def test_parse_module_finds_entry():
    hlo = jax.jit(lambda a: a + 1).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    comps, entry = parse_module(hlo)
    assert entry is not None
    assert entry in comps
