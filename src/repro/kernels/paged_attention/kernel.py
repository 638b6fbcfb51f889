"""Paged decode attention — FengHuang KV paging at kernel granularity.

The KV cache lives as fixed-size pages in a global HBM pool (the kernel's
"remote tier"); the page table is **scalar-prefetched**
(``PrefetchScalarGridSpec``) so the BlockSpec index_map can look up which
physical page to DMA into VMEM for each grid step — the hardware analogue
of the paper's Tensor Prefetcher: the next page's fetch is issued by the
Mosaic pipeline while the current page is being reduced.

Pool layout ``(P, Hkv, page, d)``: one page holds every KV head, so each
grid step DMAs ONE contiguous page of all heads, and the block's last two
dims ``(page, d)`` are the array's own — Mosaic's tiling rule (last two
block dims divisible by (8, 128) or equal to the array's) holds for any
head count.  Scales of a quantized pool are ``(P, Hkv, page)``, blocked
``(1, Hkv, page)`` under the same rule.

Grid: (batch, pages_per_seq); the KV heads are a static loop inside the
step, each with its own online-softmax state in VMEM scratch.

``extra_kv`` is the serving hot path's contract with the decode layer
scan: the pool holds strictly-past tokens (masked to ``pos <
seq_lens[b]``) and the CURRENT token's (k, v) joins as one extra
online-softmax column folded in at the final grid step — so the pool is
read-only inside the scan and the new token is written once, batched over
layers, afterwards.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(page_table_ref, seq_lens_ref,      # scalar-prefetch refs
            q_ref, k_ref, v_ref, *rest,
            hkv: int, page: int, n_pages: int, scale: float,
            has_extra: bool, has_scales: bool):
    if has_scales:
        ks_ref, vs_ref, *rest = rest
    if has_extra:
        k0_ref, v0_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(hkv):
        q = q_ref[0, h]                           # (G, d)
        k = k_ref[0, h]                           # (page, d)
        v = v_ref[0, h]
        if has_scales:
            # fused dequant: the quantized page is widened in VMEM and
            # its per-position scales multiply the (G, page) scores and
            # probabilities — q.(s*k) == (q.k)*s — so full-precision KV
            # never exists outside this tile, and the scales stay on
            # the lane axis they are stored on
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_scales:
            s = s * ks_ref[0, pl.ds(h, 1), :].astype(jnp.float32)
        pos = p_idx * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)                # (G, page)
        s = jnp.where(pos < seq_lens_ref[b], s, NEG_INF)

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        if has_scales:
            p = p * vs_ref[0, pl.ds(h, 1), :].astype(jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p_idx == n_pages - 1)
    def _flush():
        for h in range(hkv):
            m_p, l_p, acc_p = m_ref[h], l_ref[h], acc_ref[h]
            if has_extra:
                # current token's (k, v): one more online-softmax column.
                # A seq_len==0 slot gets alpha = exp(NEG_INF - s0) == 0,
                # which exactly zeroes the garbage accumulated from
                # masked pages.  extra_kv stays full precision (it is the
                # CURRENT token, never pooled).
                q = q_ref[0, h].astype(jnp.float32)
                k0 = k0_ref[0, pl.ds(h, 1), :].astype(jnp.float32)  # (1, d)
                v0 = v0_ref[0, pl.ds(h, 1), :].astype(jnp.float32)
                s0 = jax.lax.dot_general(
                    q, k0, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (G, 1)
                m_f = jnp.maximum(m_p, s0)
                alpha = jnp.exp(m_p - m_f)
                p0 = jnp.exp(s0 - m_f)
                l_p = l_p * alpha + p0
                acc_p = acc_p * alpha + p0 * v0
            o_ref[0, h] = (acc_p /
                           jnp.maximum(l_p, 1e-30)).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, seq_lens: jax.Array, *,
                    extra_kv: tuple[jax.Array, jax.Array] | None = None,
                    k_scales: jax.Array | None = None,
                    v_scales: jax.Array | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, d); pages: (P, Hkv, page, d);
    page_table: (B, n_pages) int32; seq_lens: (B,) int32;
    extra_kv: optional current-token (k0, v0), each (B, Hkv, d), attended
    in addition to the first ``seq_lens`` pooled positions;
    k_scales/v_scales: optional (P, Hkv, page) dequant scales for a
    quantized pool — DMA'd per page next to the KV tiles and applied
    inside the online-softmax loop.
    Returns (B, Hkv, G, d)."""
    b, hkv, g, d = q.shape
    n_pages = page_table.shape[1]
    if n_pages < 1:
        raise ValueError("page_table must map at least one page per row")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    page = k_pages.shape[2]
    scale = 1.0 / math.sqrt(d)
    has_extra = extra_kv is not None
    has_scales = k_scales is not None

    def per_seq(bb, p, pt, sl):
        return (bb, 0, 0, 0)

    def per_page(bb, p, pt, sl):
        # the page table drives which physical page is DMA'd
        return (pt[bb, p], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, g, d), per_seq),
        pl.BlockSpec((1, hkv, page, d), per_page),
        pl.BlockSpec((1, hkv, page, d), per_page),
    ]
    inputs = [page_table, seq_lens, q, k_pages, v_pages]
    if has_scales:
        in_specs += [
            pl.BlockSpec((1, hkv, page),
                         lambda bb, p, pt, sl: (pt[bb, p], 0, 0)),
        ] * 2
        inputs += [k_scales, v_scales]
    if has_extra:
        in_specs += [
            pl.BlockSpec((1, hkv, d), lambda bb, p, pt, sl: (bb, 0, 0)),
        ] * 2
        inputs += [extra_kv[0], extra_kv[1]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), per_seq),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, hkv=hkv, page=page, n_pages=n_pages,
                          scale=scale, has_extra=has_extra,
                          has_scales=has_scales),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(*inputs)
