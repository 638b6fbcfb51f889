"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.streamed_matmul import ops as sm
from repro.kernels.flash_attention import ops as fa
from repro.kernels.paged_attention import ops as pa
from repro.kernels.write_accumulate import ops as wa

RNG = np.random.RandomState(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# streamed matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (128, 256, 64),
                                   (100, 300, 50), (7, 513, 129)])
def test_streamed_matmul_sweep(m, k, n, dtype):
    x = jnp.asarray(RNG.randn(m, k), dtype)
    w = jnp.asarray(RNG.randn(k, n), dtype)
    out = sm.matmul(x, w, bm=64, bk=128, bn=64, interpret=True)
    ref = sm.matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@given(m=st.integers(1, 96), k=st.integers(1, 96), n=st.integers(1, 96))
@settings(max_examples=12, deadline=None)
def test_streamed_matmul_property(m, k, n):
    x = jnp.asarray(np.random.RandomState(m * 97 + k).randn(m, k), jnp.float32)
    w = jnp.asarray(np.random.RandomState(n).randn(k, n), jnp.float32)
    out = sm.matmul(x, w, bm=32, bk=32, bn=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sm.matmul_ref(x, w)),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("m,k,n", [(1, 1, 1), (1, 513, 1), (3, 5, 2),
                                   (33, 17, 9)])
def test_streamed_matmul_tiny_and_unaligned(m, k, n):
    """Shapes below / not aligned to the default block sizes clamp to
    single-block streams and still match the reference exactly."""
    x = jnp.asarray(RNG.randn(m, k), jnp.float32)
    w = jnp.asarray(RNG.randn(k, n), jnp.float32)
    out = sm.matmul(x, w, interpret=True)          # default 256/512/256 blocks
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(sm.matmul_ref(x, w)),
                               atol=2e-4, rtol=2e-4)


def test_streamed_matmul_rejects_bad_shapes():
    """Empty operands error instead of silently streaming degenerate
    1-wide blocks (the old ``min(bm, m) or 1`` clamp); so do rank and
    contraction mismatches."""
    good = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="non-empty"):
        sm.matmul(jnp.ones((0, 8), jnp.float32),
                  jnp.ones((8, 3), jnp.float32), interpret=True)
    with pytest.raises(ValueError, match="non-empty"):
        sm.matmul(good, jnp.ones((8, 0), jnp.float32), interpret=True)
    with pytest.raises(ValueError, match="non-empty"):
        sm.matmul(jnp.ones((4, 0), jnp.float32),
                  jnp.ones((0, 8), jnp.float32), interpret=True)
    with pytest.raises(ValueError, match="contraction mismatch"):
        sm.matmul(good, jnp.ones((9, 3), jnp.float32), interpret=True)
    with pytest.raises(ValueError, match="2-D"):
        sm.matmul(jnp.ones((2, 4, 8), jnp.float32), good, interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 13), (False, 0)])
@pytest.mark.parametrize("sq,sk,hq,hkv", [(64, 64, 4, 4), (64, 64, 4, 2),
                                          (50, 50, 2, 1), (32, 96, 4, 2)])
def test_flash_attention_sweep(sq, sk, hq, hkv, causal, window, dtype):
    d = 32
    q = jnp.asarray(RNG.randn(2, sq, hq, d), dtype) * 0.3
    k = jnp.asarray(RNG.randn(2, sk, hkv, d), dtype) * 0.3
    v = jnp.asarray(RNG.randn(2, sk, hkv, d), dtype)
    out = fa.attention(q, k, v, causal=causal, window=window, bq=32, bk=32,
                       interpret=True)
    ref = fa.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_matches_model_layer_path():
    """The kernel and models.layers.flash_attention agree (same oracle)."""
    from repro.models.layers import flash_attention as jnp_flash
    q = jnp.asarray(RNG.randn(1, 64, 4, 32), jnp.float32) * 0.3
    k = jnp.asarray(RNG.randn(1, 64, 2, 32), jnp.float32) * 0.3
    v = jnp.asarray(RNG.randn(1, 64, 2, 32), jnp.float32)
    a = fa.attention(q, k, v, causal=True, bq=32, bk=32, interpret=True)
    b = jnp_flash(q, k, v, causal=True, q_block=32, kv_block=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hkv,g,npages,page", [(2, 2, 2, 4, 8),
                                                 (3, 1, 4, 3, 16),
                                                 (1, 4, 1, 6, 4)])
def test_paged_attention_sweep(b, hkv, g, npages, page, dtype, extra):
    d = 32
    pool = npages * b + 1
    kp = jnp.asarray(RNG.randn(pool, hkv, page, d), dtype) * 0.3
    vp = jnp.asarray(RNG.randn(pool, hkv, page, d), dtype)
    q = jnp.asarray(RNG.randn(b, hkv, g, d), dtype) * 0.3
    table = jnp.asarray(
        1 + np.arange(b * npages).reshape(b, npages), jnp.int32)
    lens = jnp.asarray(RNG.randint(1, npages * page + 1, size=(b,)),
                       jnp.int32)
    # extra_kv = the serving hot path's current-token column (the pool is
    # read-only in the decode scan; the new token joins at the flush step)
    kv0 = (jnp.asarray(RNG.randn(b, hkv, d), dtype) * 0.3,
           jnp.asarray(RNG.randn(b, hkv, d), dtype)) if extra else None
    out = pa.attend(q, kp, vp, table, lens, kv0, interpret=True)
    ref = pa.attend_ref(q, kp, vp, table, lens, kv0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_block_pool_lifecycle():
    from repro.memory import BlockPoolResidency
    pool = BlockPoolResidency(num_pages=8, page_size=4, kv_heads=2,
                              head_dim=8)
    pool.alloc_seq(1)
    k_blk = jnp.stack([jnp.full((2, 8), float(i)) for i in range(6)])
    pool.append_block(1, k_blk, -k_blk)   # 6 tokens cross a page boundary
    assert pool.manager.lens[1] == 6
    assert len(pool.manager.pages[1]) == 2
    t = pool.batch_tables([1], 3)
    assert t.shape == (1, 3)
    pool.free_seq(1)
    assert 1 not in pool.manager.pages


# ---------------------------------------------------------------------------
# write accumulate
# ---------------------------------------------------------------------------

@given(n=st.integers(2, 12), rows=st.integers(1, 40),
       cols=st.integers(1, 80))
@settings(max_examples=15, deadline=None)
def test_write_accumulate_property(n, rows, cols):
    sh = jnp.asarray(np.random.RandomState(n).randn(n, rows, cols),
                     jnp.float32)
    out = wa.accumulate(sh, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(wa.accumulate_ref(sh)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_write_accumulate_dtypes(dtype):
    sh = jnp.asarray(RNG.randn(8, 64, 128), dtype)
    out = wa.accumulate(sh, interpret=True)
    ref = wa.accumulate_ref(sh)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_write_accumulate_commutativity():
    """§3.3.1: accumulation is order-independent (commutative reduction)."""
    sh = jnp.asarray(RNG.randn(6, 32, 64), jnp.float32)
    perm = np.random.RandomState(1).permutation(6)
    a = wa.accumulate(sh, interpret=True)
    b = wa.accumulate(sh[perm], interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
