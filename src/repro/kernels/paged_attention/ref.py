"""Pure-jnp oracle for paged decode attention."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _is_f8(dtype) -> bool:
    """Single-byte float pool dtype (fp8_e4m3 / fp8_e5m2)."""
    dt = jnp.dtype(dtype)
    return dt.itemsize == 1 and jnp.issubdtype(dt, jnp.floating)


@functools.lru_cache(maxsize=None)
def _f8_lut(dtype_name: str):
    """(256,) fp32 table holding the convert of every fp8 bit pattern —
    the dequant LUT for :func:`to_f32`.  Built host-side (numpy) so the
    cached value is a constant, never a leaked tracer."""
    import numpy as np
    return np.arange(256, dtype=np.uint8).view(
        jnp.dtype(dtype_name)).astype(np.float32)


def gatherable_view(pool: jax.Array) -> jax.Array:
    """uint8 bit-view of an fp8 pool; any other pool unchanged.

    XLA:CPU's gather falls off the fast byte-copy path for float8
    element types (~8x slower than the identical gather on int8/uint8),
    and ``convert_element_type`` f8->f32 over the gathered view is
    likewise scalar — together the source of the fp8 serving throughput
    cliff (server_paged_fp8 at ~0.64x bf16 before this fix).  Gathering
    the same bytes as uint8 and converting through :func:`view_to_f32`'s
    256-entry LUT is bit-identical and restores int8-class speed."""
    if _is_f8(pool.dtype):
        return jax.lax.bitcast_convert_type(pool, jnp.uint8)
    return pool


def to_f32(x: jax.Array) -> jax.Array:
    """fp32 view of gathered pool values.  fp8 goes through the 256-entry
    LUT (bit-identical to ``astype(float32)`` by construction — the LUT
    IS that convert, precomputed over all 256 patterns) instead of the
    scalar ``convert_element_type`` path; everything else casts."""
    if _is_f8(x.dtype):
        return jnp.take(jnp.asarray(_f8_lut(x.dtype.name)),
                        jax.lax.bitcast_convert_type(
                            x, jnp.uint8).astype(jnp.int32), axis=0)
    return x.astype(jnp.float32)


def take_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """``pool[page_table]`` with fp8 pools routed through the uint8
    bit-view (see :func:`gatherable_view`) and bitcast back to the pool
    dtype — bit-identical, gathers at int8-class speed.  The bitcast
    round trip is a metadata op that XLA fuses away; pairing a gathered
    fp8 result with :func:`to_f32` keeps the whole read path off the
    slow fp8 gather/convert kernels."""
    g = gatherable_view(pool)[page_table]
    if g.dtype != pool.dtype:
        g = jax.lax.bitcast_convert_type(g, pool.dtype)
    return g


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize the per-sequence view of a page pool.

    pages: (P, Hkv, page, d); page_table: (B, n_pages) int32.
    Returns (B, Hkv, n_pages * page, d) — the cache layout
    :func:`repro.models.layers.decode_attention` expects, with gathered
    position ``i`` holding absolute position ``i`` (pages are in order).
    """
    b, n_pages = page_table.shape
    hkv, page, d = pages.shape[1:]
    g = take_pages(pages, page_table)       # (B, n_pages, Hkv, page, d)
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, n_pages * page, d)


def gather_scales(scales: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize the per-sequence view of a per-page scale array.

    scales: (P, Hkv, page) — one dequant scale per (head, token slot);
    page_table: (B, n_pages) int32.  Returns (B, Hkv, n_pages * page),
    aligned position-for-position with :func:`gather_pages`.
    """
    b, n_pages = page_table.shape
    hkv, page = scales.shape[1:]
    g = scales[page_table]                  # (B, n_pages, Hkv, page)
    return g.transpose(0, 2, 1, 3).reshape(b, hkv, n_pages * page)


def paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens,
                        extra_kv=None, k_scales=None, v_scales=None):
    """Decode attention over a paged KV cache.

    q:          (B, Hkv, G, d)       one query token, grouped heads
    k_pages:    (P, Hkv, page, d)    global page pool
    v_pages:    (P, Hkv, page, d)
    page_table: (B, pages_per_seq)   int32 page ids
    seq_lens:   (B,)                 valid tokens per sequence
    extra_kv:   optional current-token (k0, v0), each (B, Hkv, d),
                attended as one extra column past the pooled positions
    k_scales:   optional (P, Hkv, page) dequant scales for a quantized
                pool — multiplied into the fp32 view inline, so the
                full-precision KV never materializes outside this gather
    v_scales:   same, for the value pool
    returns     (B, Hkv, G, d)
    """
    b, hkv, g, d = q.shape
    pages_per_seq = page_table.shape[1]
    page = k_pages.shape[2]

    # fp8 pools gather as a uint8 bit-view and dequantize through the
    # 256-entry convert LUT (bit-identical; see take_pages / to_f32) —
    # the fix for the fp8 serving throughput cliff
    k = gather_pages(k_pages, page_table)      # (B, Hkv, S, d)
    v = gather_pages(v_pages, page_table)
    if k_scales is not None:
        ks = gather_scales(k_scales, page_table)
        k = to_f32(k) * ks.astype(jnp.float32)[..., None]
    elif _is_f8(k.dtype):             # scale-less fp8: plain convert
        k = to_f32(k)
    if v_scales is not None:
        vs = gather_scales(v_scales, page_table)
        v = to_f32(v) * vs.astype(jnp.float32)[..., None]
    elif _is_f8(v.dtype):
        v = to_f32(v)

    s = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    pos = jnp.arange(pages_per_seq * page)[None, :]
    valid = pos < seq_lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    if extra_kv is not None:
        k0, v0 = extra_kv
        s0 = jnp.einsum("bhgd,bhd->bhg", q.astype(jnp.float32),
                        k0.astype(jnp.float32)) / math.sqrt(d)
        s = jnp.concatenate([s, s0[..., None]], axis=-1)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / p.sum(axis=-1, keepdims=True)
    if extra_kv is not None:
        o = jnp.einsum("bhgs,bhsd->bhgd", p[..., :-1],
                       v.astype(jnp.float32))
        o = o + p[..., -1][..., None] * extra_kv[1][:, :, None, :].astype(
            jnp.float32)
    else:
        o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
