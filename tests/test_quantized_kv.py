"""Quantized KV page pools: round-trip error bounds, per-page scale
correctness, swap/restore and kill/restore bit-identity, prefix-cache
stability, and the fused-dequant attention read path.

Two-tier correctness contract under test:

* quantized-vs-quantized is BIT-IDENTICAL across preemption, snapshot
  restart and sharding — swap/restore round-trips the quantized bytes
  and their bf16 scales verbatim, and sampling keys off (uid, position);
* quantized-vs-bf16 is APPROXIMATE: bounded per-vector round-trip error
  (gated end-to-end in ``check_bench_schema.py``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ops import BlockManager, attend_ref
from repro.memory.swap import PageSwapper
from repro.models import layers as L
from repro.models.base import ModelConfig
from repro.runtime import ft
from repro.runtime.serve import BatchedServer

PAGE = 4
MAX_SEQ = 64
SMALL_POOL = 18          # oversubscribed: forces preemption (see chaos)

KV_DTYPES = [("int8", jnp.int8, 127.0), ("fp8_e4m3", jnp.float8_e4m3fn,
                                         448.0)]


@pytest.fixture(scope="module", params=["int8", "fp8_e4m3"])
def quant_model(request):
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, remat=False, page_size=PAGE,
                              kv_dtype=request.param)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _server(quant_model, **kw):
    model, params = quant_model
    kw.setdefault("batch_size", 3)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("audit", True)
    return BatchedServer(model, params, **kw)


def _drive(server, reqs, max_rounds=50):
    finished = []
    for _ in range(max_rounds):
        finished += server.run_once()
        if all(r.done.is_set() for r in reqs):
            return finished
    raise AssertionError("requests stuck")


def _submit_three(server):
    return [server.submit(np.arange(1, 5, dtype=np.int32),
                          max_new_tokens=24) for _ in range(3)]


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_kv_dtype_none_is_full_precision():
    cfg = get_config("qwen2.5-14b").reduced()
    assert cfg.kv_dtype is None and not cfg.kv_quantized
    assert cfg.kv_pool_dtype() == cfg.dtype


def test_unknown_kv_dtype_rejected():
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              kv_dtype="int4")
    with pytest.raises(ValueError, match="kv_dtype"):
        cfg.kv_pool_dtype()


@pytest.mark.parametrize("name,dt,qmax", KV_DTYPES)
def test_kv_dtype_resolution(name, dt, qmax):
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              kv_dtype=name)
    assert cfg.kv_quantized
    assert cfg.kv_pool_dtype() == dt
    assert cfg.kv_qmax() == qmax


def test_quantized_pool_shapes_and_dtypes(quant_model):
    model, _ = quant_model
    cfg = model.cfg
    cache = model.init_paged_cache(6)
    assert cache["k_pages"].dtype == cfg.kv_pool_dtype()
    assert cache["k_scale"].dtype == jnp.bfloat16
    assert cache["k_scale"].shape == cache["k_pages"].shape[:-1]
    assert cache["v_scale"].shape == cache["v_pages"].shape[:-1]


def test_quantized_bytes_per_page_halves_pool(quant_model):
    """True per-page bytes (scales INCLUDED) must be <= 0.55x the bf16
    pool — the capacity headline the benchmark gates."""
    model, _ = quant_model
    cfg = model.cfg
    m = BlockManager(num_pages=8, page_size=cfg.page_size)
    bf16 = m.bytes_per_page(cfg.padded_kv_heads, cfg.head_dim, 2,
                            cfg.num_layers)
    qdt = jnp.dtype(cfg.kv_pool_dtype()).itemsize
    quant = m.bytes_per_page(cfg.padded_kv_heads, cfg.head_dim, qdt,
                             cfg.num_layers, scale_itemsize=2)
    assert quant / bf16 <= 0.55
    # and it matches the real allocation exactly
    cache = model.init_paged_cache(8)
    from repro.memory import tree_bytes
    assert quant * 8 == tree_bytes(cache)


# ---------------------------------------------------------------------------
# round-trip error bounds + per-page scales
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,dt,qmax", KV_DTYPES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_round_trip_error_bound(name, dt, qmax, seed):
    """Per-vector absmax quantization: |x - dq(q(x))| <= amax/qmax per
    int8 step, or one fp8 ulp (2^-3 relative) — checked against the
    per-vector amax, over magnitudes spanning 1e-3..1e3."""
    key = jax.random.PRNGKey(seed)
    mags = jnp.asarray([1e-3, 1e-1, 1.0, 1e2, 1e3])[:, None, None]
    x = jax.random.normal(key, (5, 16, 64), jnp.float32) * mags
    q, s = L.kv_pool_quantize(x, dt, qmax)
    assert q.dtype == dt and s.dtype == jnp.bfloat16
    back = L.kv_dequantize(q, s, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    # bf16 scale storage adds <= 2^-8 relative on top of the quant step
    if name == "int8":
        bound = amax / qmax * (0.5 + 1e-2) + amax * 2 ** -8
    else:
        bound = amax * 2.0 ** -3 + amax * 2 ** -8
    assert jnp.max(jnp.abs(back - x) - bound) <= 0, \
        float(jnp.max(jnp.abs(back - x) / jnp.maximum(amax, 1e-9)))


@pytest.mark.parametrize("name,dt,qmax", KV_DTYPES)
def test_round_trip_is_idempotent(name, dt, qmax):
    """Quantizing a dequantized tensor reproduces the same bytes — the
    write/read fixed point the bit-identity contract needs."""
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4, 32), jnp.float32)
    q1, s1 = L.kv_pool_quantize(x, dt, qmax)
    back = L.kv_dequantize(q1, s1, jnp.float32)
    q2, s2 = L.kv_pool_quantize(back, dt, qmax)
    back2 = L.kv_dequantize(q2, s2, jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(back2))


def test_zero_vectors_survive_quantization():
    """Null pages and padding are all-zero: amax 0 must not divide by
    zero, and dequant must give back exact zeros."""
    for _, dt, qmax in KV_DTYPES:
        q, s = L.kv_pool_quantize(jnp.zeros((2, 3, 8)), dt, qmax)
        back = L.kv_dequantize(q, s, jnp.float32)
        assert np.all(np.isfinite(np.asarray(s, np.float32)))
        np.testing.assert_array_equal(np.asarray(back), 0.0)


def test_per_page_scales_do_not_bleed(quant_model):
    """A prompt whose pages differ in magnitude by 1e3: each written
    page must dequantize within ITS OWN amax bound.  A shared (per-pool
    or per-sequence) scale would crush the small page to zero."""
    model, params = quant_model
    cfg = model.cfg
    seq = 3 * PAGE                     # three full pages
    cache = model.init_paged_cache(4)
    pages = jnp.asarray([[1, 2, 3]], jnp.int32)
    tokens = jnp.asarray(np.arange(1, seq + 1)[None], jnp.int32)
    _, cache = jax.jit(model.prefill_paged)(params, tokens, cache, pages)
    ks = np.asarray(cache["k_scale"], np.float32)    # (L, P, Hkv, page)
    live = ks[:, 1:4]
    assert np.all(live > 0)
    # scales are PER page slot: pages see different activations, so a
    # constant scale across all slots would mean the per-slot amax never
    # reached storage
    assert len({round(float(v), 10) for v in live.ravel()}) > 1
    # dequantized pool values stay within each slot's own scale * qmax
    kq = np.asarray(cache["k_pages"][:, 1:4], np.float32)
    assert np.all(np.abs(kq) <= cfg.kv_qmax() + 1e-6)


# ---------------------------------------------------------------------------
# fused-dequant attention read path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,dt,qmax", KV_DTYPES)
def test_kernel_matches_ref_with_scales(name, dt, qmax):
    """The Pallas kernel (interpret mode) and the gather oracle must
    agree on quantized pools — same online-softmax, same fused dequant."""
    key = jax.random.PRNGKey(0)
    b, hkv, g, hd, n_pages, page, pool = 2, 2, 2, 64, 3, 8, 8
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (pool, hkv, page, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (pool, hkv, page, hd), jnp.float32)
    k_pages, k_scales = L.kv_pool_quantize(kf, dt, qmax)
    v_pages, v_scales = L.kv_pool_quantize(vf, dt, qmax)
    table = jax.random.randint(ks[3], (b, n_pages), 1, pool, jnp.int32)
    seq_lens = jnp.asarray([13, 22], jnp.int32)
    ref = attend_ref(q, k_pages, v_pages, table, seq_lens,
                     k_scales=k_scales, v_scales=v_scales)
    out = paged_attention(q, k_pages, v_pages, table, seq_lens,
                          k_scales=k_scales, v_scales=v_scales,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lens", [(128, 129), (37, 0)])
@pytest.mark.parametrize("name,dt,qmax", KV_DTYPES)
def test_kernel_blocks_of_pages_with_scales(name, dt, qmax, lens):
    """Blocks of 8 pages of 16 positions: each page's scales are moved to
    its own lanes of the block's score tile.  The kernel matches the
    gather oracle at a block edge, one past it, and for an empty slot."""
    key = jax.random.PRNGKey(1)
    b, hkv, g, hd, n_pages, page, pool = 2, 2, 5, 32, 16, 16, 40
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (pool, hkv, page, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (pool, hkv, page, hd), jnp.float32)
    k_pages, k_scales = L.kv_pool_quantize(kf, dt, qmax)
    v_pages, v_scales = L.kv_pool_quantize(vf, dt, qmax)
    table = jax.random.randint(ks[3], (b, n_pages), 1, pool, jnp.int32)
    kv0 = (jax.random.normal(ks[4], (b, hkv, hd), jnp.float32),
           jax.random.normal(ks[5], (b, hkv, hd), jnp.float32))
    seq_lens = jnp.asarray(lens, jnp.int32)
    ref = attend_ref(q, k_pages, v_pages, table, seq_lens, extra_kv=kv0,
                     k_scales=k_scales, v_scales=v_scales)
    out = paged_attention(q, k_pages, v_pages, table, seq_lens,
                          extra_kv=kv0, k_scales=k_scales,
                          v_scales=v_scales, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_scales_must_come_in_pairs():
    q = jnp.zeros((1, 1, 1, 8))
    kp = jnp.zeros((2, 4, 1, 8), jnp.int8)
    sc = jnp.zeros((2, 4, 1), jnp.bfloat16)
    with pytest.raises(ValueError, match="k_scales and v_scales"):
        paged_attention(q, kp, kp, jnp.zeros((1, 1), jnp.int32),
                        jnp.ones((1,), jnp.int32), k_scales=sc,
                        interpret=True)


# ---------------------------------------------------------------------------
# PageSwapper: scales ride along bit-identically
# ---------------------------------------------------------------------------

def test_swap_round_trip_preserves_quantized_bytes(quant_model):
    model, _ = quant_model
    cache = model.init_paged_cache(10)
    key = jax.random.PRNGKey(5)
    k1, k2 = jax.random.split(key)
    qdt, qmax = model.cfg.kv_pool_dtype(), model.cfg.kv_qmax()
    kq, ks = L.kv_pool_quantize(
        jax.random.normal(k1, cache["k_pages"].shape, jnp.float32),
        qdt, qmax)
    vq, vs = L.kv_pool_quantize(
        jax.random.normal(k2, cache["v_pages"].shape, jnp.float32),
        qdt, qmax)
    cache = {"k_pages": kq, "v_pages": vq, "k_scale": ks, "v_scale": vs}
    want_k = np.asarray(cache["k_pages"][:, [2, 5, 7]])
    want_s = np.asarray(cache["k_scale"][:, [2, 5, 7]])

    sw = PageSwapper()
    h = sw.swap_out(cache, [2, 5, 7])
    assert h.k_scale is not None and h.v_scale is not None
    # nbytes mixes pool-dtype values with bf16 scales
    assert h.nbytes == 2 * (want_k.size * want_k.dtype.itemsize
                            + want_s.size * 2)
    np.testing.assert_array_equal(
        h.k.view(np.uint8), want_k.view(np.uint8))
    np.testing.assert_array_equal(
        h.k_scale.view(np.uint8), want_s.view(np.uint8))
    # restore into DIFFERENT page ids: bytes land verbatim
    cache = sw.swap_in(cache, [1, 3, 8], h)
    np.testing.assert_array_equal(
        np.asarray(cache["k_pages"][:, [1, 3, 8]]).view(np.uint8),
        want_k.view(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(cache["k_scale"][:, [1, 3, 8]]).view(np.uint8),
        want_s.view(np.uint8))


@pytest.mark.parametrize("temp", [0.0, 0.7])
def test_quantized_preemption_bit_identical(quant_model, temp):
    """Oversubscribed quantized pool: preempt/swap/resume must not
    change a single token vs the uncontended quantized run."""
    ref_srv = _server(quant_model, temperature=temp)
    ref = _submit_three(ref_srv)
    _drive(ref_srv, ref)
    assert ref_srv.stats["preemptions"] == 0

    srv = _server(quant_model, temperature=temp, num_pages=SMALL_POOL)
    got = _submit_three(srv)
    _drive(srv, got)
    assert srv.stats["preemptions"] >= 1
    assert srv.stats["resumes"] >= 1
    assert srv.stats["sheds"] == 0
    for a, b in zip(ref, got):
        assert a.output == b.output, (temp, a.uid, a.output, b.output)
        assert b.error is None


@pytest.mark.parametrize("temp", [0.0, 0.7])
def test_quantized_kill_and_restore_bit_identical(quant_model, tmp_path,
                                                  temp):
    """Snapshot mid-decode -> disk -> fresh server: the quantized pages
    and their scales round-trip through npz storage views and every
    sequence finishes with the uninterrupted run's tokens."""
    ref_srv = _server(quant_model, temperature=temp,
                      num_pages=SMALL_POOL)
    ref = _submit_three(ref_srv)
    _drive(ref_srv, ref)

    srv = _server(quant_model, temperature=temp, num_pages=SMALL_POOL)
    reqs = _submit_three(srv)
    early = srv.run_once(max_blocks=1)
    snap = srv.snapshot()
    assert any("k_scale" in s for s in snap["sequences"]
               if s["pos"]), "snapshot dropped the quantized scales"
    path = ft.save_server_snapshot(tmp_path / "qserve_ckpt", snap)
    del srv

    srv2 = _server(quant_model, temperature=temp, num_pages=SMALL_POOL)
    ft.restore_server(srv2, ft.load_server_snapshot(path))
    finished = list(early)
    for _ in range(50):
        finished += srv2.run_once()
        if len(finished) == 3:
            break
    by_uid = {r.uid: r for r in finished}
    assert len(by_uid) == 3
    for a in ref:
        b = by_uid[a.uid]
        assert a.output == b.output, (a.uid, a.output, b.output)
        assert b.error is None


# ---------------------------------------------------------------------------
# prefix cache on quantized pools
# ---------------------------------------------------------------------------

def test_quantized_prefix_sharing_deterministic(quant_model):
    """The prefix hash keys on TOKEN bytes (precision-independent), so
    quantized servers share prefix pages; shared admissions must be
    deterministic run-to-run and bit-identical across restarts."""
    sys_toks = np.arange(3, 15, dtype=np.int32)        # 3 whole pages

    def run():
        srv = _server(quant_model, prefix_cache=True)
        reqs = [srv.submit(
            np.concatenate([sys_toks, np.asarray([50 + i, 60 + i],
                                                 np.int32)]),
            max_new_tokens=16) for i in range(3)]
        _drive(srv, reqs)
        return [tuple(r.output) for r in reqs], srv

    out1, srv1 = run()
    out2, srv2 = run()
    assert srv1.stats["prefix_hits"] > 0
    assert srv1.stats["prefix_shared_pages"] > 0
    assert out1 == out2, "quantized prefix sharing is nondeterministic"


def test_quantized_prefix_hash_matches_bf16_hash(quant_model):
    """Same tokens -> same prefix index keys regardless of kv_dtype:
    the index is over padded token bytes, never pool bytes."""
    model, params = quant_model
    cfg_bf16 = dataclasses.replace(model.cfg, kv_dtype=None)
    sys_toks = np.arange(3, 15, dtype=np.int32)

    def keys(m_cfg):
        srv = BatchedServer(build_model(m_cfg), params, batch_size=3,
                            max_seq=MAX_SEQ, page_size=PAGE,
                            prefix_cache=True, audit=True)
        # spy on registration: index entries are dropped as soon as the
        # last reference to a shared page is freed, which can happen
        # inside a single run_once for short requests
        seen = set()
        orig = srv.manager.register_prefix

        def spy(key, page_id):
            seen.add(key)
            return orig(key, page_id)

        srv.manager.register_prefix = spy
        reqs = [srv.submit(
            np.concatenate([sys_toks, np.asarray([50 + i], np.int32)]),
            max_new_tokens=8) for i in range(2)]
        _drive(srv, reqs)
        return seen

    kq, kb = keys(model.cfg), keys(cfg_bf16)
    assert kq and kq == kb


# ---------------------------------------------------------------------------
# sharded quantized serving (subprocess, forced 8 host devices)
# ---------------------------------------------------------------------------

SHARDED_QUANT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, numpy as np
from repro.configs import get_config, build_model
from repro.launch.mesh import make_serving_mesh
from repro.runtime.serve import BatchedServer

cfg = get_config("qwen2.5-14b").reduced()
cfg = dataclasses.replace(cfg, remat=False, page_size=4, kv_dtype="int8")
params = build_model(cfg).init(jax.random.PRNGKey(0))

def serve(mesh, num_pages):
    srv = BatchedServer(build_model(cfg), params, batch_size=3, max_seq=64,
                        page_size=4, num_pages=num_pages, temperature=0.7,
                        paged=True, mesh=mesh, audit=True)
    reqs = [srv.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=24)
            for _ in range(3)]
    for _ in range(50):
        srv.run_once()
        if all(r.done.is_set() for r in reqs):
            break
    return [tuple(r.output) for r in reqs], srv

mesh = make_serving_mesh(model=2)
single, _ = serve(None, None)              # unsharded, uncontended
ref, _ = serve(mesh, None)                 # sharded, uncontended
got, srv = serve(mesh, 18)                 # sharded + preemption
assert srv.stats["model_shards"] == 2
assert srv.stats["preemptions"] >= 1, srv.stats
assert srv.stats["resumes"] >= 1, srv.stats
assert ref == single, "sharded quantized tokens diverged from 1-device"
assert got == ref, "sharded quantized preemption diverged"
print("SHARDED_QUANT_OK")
"""


@pytest.mark.slow
def test_sharded_quantized_preemption_bit_identical():
    """Head-sharded quantized pools (scales shard with their pages):
    mesh serving and preempt/swap/resume across the "model" axis must
    keep every token identical to the single-device quantized run."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SHARDED_QUANT_SCRIPT, src],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert "SHARDED_QUANT_OK" in out.stdout, \
        out.stdout[-1500:] + out.stderr[-3000:]


# ---------------------------------------------------------------------------
# ledger: quantized pool accounting
# ---------------------------------------------------------------------------

def test_quantized_server_accounts_true_bytes(quant_model):
    """kv_bytes_in_use charges pool-dtype values PLUS bf16 scales, and
    the per-page rate matches the real allocation."""
    from repro.memory import tree_bytes
    srv = _server(quant_model)
    req = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    _drive(srv, [req])
    per_page = tree_bytes(srv.cache) // srv.num_pages
    assert srv.kv_bytes_capacity() == per_page * srv.num_pages
    # hwm pages x true per-page bytes is what the benchmark reports
    assert srv.manager.hwm > 0
