"""TensorPager: paged execution must be bit-compatible with unpaged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import memory as pager


@pytest.fixture(scope="module")
def ws():
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.randn(6, 16, 16), jnp.float32) * 0.1


def body(c, w):
    return jnp.tanh(c @ w), c.sum()


def test_supports_memory_spaces():
    assert pager.supports_memory_spaces()


def test_paged_scan_matches_plain(ws):
    c0 = jnp.ones((2, 16))
    ref_c, ref_y = jax.jit(
        lambda c, w: jax.lax.scan(body, c, w))(c0, ws)
    ws_host = pager.host_put(ws)
    got_c, got_y = jax.jit(
        lambda c, w: pager.paged_scan(body, c, w,
                                      config=pager.PagerConfig(enabled=True))
    )(c0, ws_host)
    np.testing.assert_allclose(ref_c, got_c, atol=1e-6)
    np.testing.assert_allclose(ref_y, got_y, atol=1e-6)


def test_paged_scan_disabled_is_plain(ws):
    c0 = jnp.ones((2, 16))
    a = jax.jit(lambda c, w: pager.paged_scan(
        body, c, w, config=pager.PagerConfig(enabled=False)))(c0, ws)
    b = jax.jit(lambda c, w: jax.lax.scan(body, c, w))(c0, ws)
    np.testing.assert_allclose(a[0], b[0], atol=0)


def test_grad_through_paging(ws):
    c0 = jnp.ones((2, 16))

    def loss(c, w):
        out, _ = pager.paged_scan(
            lambda cc, ww: (jnp.tanh(cc @ ww), None), c, w,
            config=pager.PagerConfig(enabled=True))
        return jnp.sum(out ** 2)

    def loss_plain(c, w):
        out, _ = jax.lax.scan(
            lambda cc, ww: (jnp.tanh(cc @ ww), None), c, w)
        return jnp.sum(out ** 2)

    ws_host = pager.host_put(ws)
    g1 = jax.jit(jax.grad(loss, argnums=1))(c0, ws_host)
    g2 = jax.jit(jax.grad(loss_plain, argnums=1))(c0, ws)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


def test_paged_scan_cache_matches_loop(ws):
    """Cache-in-carry scan == hand-rolled python loop."""
    L = ws.shape[0]
    cache = jnp.zeros((L, 2, 16))

    def cbody(c, w, cl):
        c = jnp.tanh(c @ w + cl.sum() * 0.01)
        return c, cl + 1.0

    c0 = jnp.ones((2, 16))
    got_c, got_cache = jax.jit(lambda c, w, ca: pager.paged_scan_cache(
        cbody, c, w, ca, config=pager.PagerConfig(enabled=False)))(
            c0, ws, cache)

    ref_c, ref_cache = c0, cache
    for i in range(L):
        ref_c, upd = cbody(ref_c, ws[i], ref_cache[i])
        ref_cache = ref_cache.at[i].set(upd)
    np.testing.assert_allclose(got_c, ref_c, atol=1e-6)
    np.testing.assert_allclose(got_cache, ref_cache, atol=1e-6)

    # paged variant agrees too
    ws_host = pager.host_put(ws)
    got2_c, got2_cache = jax.jit(lambda c, w, ca: pager.paged_scan_cache(
        cbody, c, w, ca, config=pager.PagerConfig(enabled=True)))(
            c0, ws_host, cache)
    np.testing.assert_allclose(got2_c, ref_c, atol=1e-6)
    np.testing.assert_allclose(got2_cache, ref_cache, atol=1e-6)


def test_resident_window_bytes(ws):
    per_layer = 16 * 16 * 4
    assert pager.resident_window_bytes(ws, 1) == 2 * per_layer
    assert pager.resident_window_bytes(ws, 3) == 4 * per_layer


def test_page_roundtrip():
    x = jnp.arange(32.0)
    h = pager.page_out(x)
    d = pager.page_in(h)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(x))


def test_paged_scan_streams_pinned_host_weights(ws):
    """Weights at rest in pinned host memory stream through the paged
    scan and give the unpaged scan's result; eager page_out lands in
    host memory and page_in brings it back to the device."""
    c0 = jnp.ones((2, 16))
    ws_host = pager.host_put(ws)
    assert ws_host.sharding.memory_kind == "pinned_host"
    ref = jax.jit(lambda c, w: jax.lax.scan(body, c, w))(c0, ws)
    got = jax.jit(lambda c, w: pager.paged_scan(
        body, c, w, config=pager.PagerConfig(enabled=True)))(c0, ws_host)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    out = pager.page_out(got[0])
    assert out.sharding.memory_kind == "pinned_host"
    assert pager.page_in(out).sharding.memory_kind == "device"


def test_offloaded_cache_rests_in_host_memory(ws):
    """offload_kv: a host-resident cache is paged in for the step, comes
    back from the jitted step in device memory, and the orchestrator's
    rest placement puts it back in the remote tier — checked, not
    assumed."""
    L = ws.shape[0]
    cfg = pager.PagerConfig(enabled=True, offload_kv=True)
    mem = pager.MemoryOrchestrator.plan(pager_config=cfg)
    cache = mem.place_kv_pool({"k_pages": jnp.zeros((L, 2, 16))})
    assert cache["k_pages"].sharding.memory_kind == "pinned_host"

    def cbody(c, w, cl):
        return jnp.tanh(c @ w + cl.sum() * 0.01), cl + 1.0

    step = mem.donating_jit(lambda c, w, ca: mem.layer_scan_cache(
        lambda h, lw, cl: (lambda o: (o[0], {"k_pages": o[1]}))(
            cbody(h, lw, cl["k_pages"])), c, w, ca), donate_argnums=(2,))
    _, new = step(jnp.ones((2, 16)), pager.host_put(ws), cache)
    assert new["k_pages"].sharding.memory_kind == "device"
    rested = mem.rest_kv_pool(new)
    assert rested["k_pages"].sharding.memory_kind == "pinned_host"
    np.testing.assert_array_equal(np.asarray(rested["k_pages"]),
                                  np.ones((L, 2, 16)))
