"""Paged decode attention — FengHuang KV paging at kernel granularity.

The KV cache lives as fixed-size pages in a global HBM pool (the kernel's
"remote tier").  The page table and the lengths are **scalar-prefetched**
(``PrefetchScalarGridSpec``) into SMEM; the pools stay in HBM
(``memory_space=pltpu.HBM``: under ``pl.ANY`` the compiler places a
small pool in VMEM) and the kernel starts its own page DMAs from the
table — the hardware analogue of the paper's Tensor Prefetcher.  A DMA
moves whole 128-lane rows, so a quantized pool's ``(P, Hkv, page)``
scales are padded out to 128 lanes on each call, a copy of the scale
pools that only quantized pools pay.

Grid: ``(batch,)``, one step per slot.  Inside a step a ``fori_loop``
walks the slot's **live pages only**, in compute blocks of
:func:`block_pages` pages (128 positions at page 16, so each KV head's
score tile is a lane-dense ``(G, 128)``).  Block ``j + 1``'s pages are
DMA'd into the other half of a double-buffered VMEM buffer
``(2, Hkv, block_pages, page, d)`` — one copy per page for K and for V,
spread over the heads, and for a quantized pool one per page of its
``(Hkv, page)`` scales — while block ``j`` is reduced.  All KV heads are
reduced together, as batched matmuls with one online-softmax state per
head in VMEM scratch.  The kernel's time therefore follows the live KV,
not ``batch × table width``.

Which pages are live (:func:`pages_fetched`): slot ``b`` has
``ceil(seq_lens[b] / page)`` of them, at most the table width, when
``page_table[b, 0]`` is a real page, and none when it is the null page
0.  Page 0 is never handed out: table padding and the rows of evicted
or dead slots point at it, while a slot holding any pooled position has
column 0 mapped.  So a dead slot — row nulled, its length frozen at its
last position — costs one grid step and no DMA.  Columns past the live
pages are neither fetched nor computed, except that the last block is
fetched whole (its pages past ``seq_lens`` are real or the null page,
and masked).

``extra_kv`` is the serving hot path's contract with the decode layer
scan: the pool holds strictly-past tokens (masked to ``pos <
seq_lens[b]``) and the CURRENT token's (k, v) joins as one extra
online-softmax column folded in at the end of the slot's step — so the
pool is read-only inside the scan and the new token is written once,
batched over layers, afterwards.  A slot with no live pages gets the
``extra_kv`` column alone.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def block_pages(page: int, width: int) -> int:
    """Pages one compute block spans: enough to fill the 128 lanes of a
    score tile, and a divisor of the table width ``width`` — so
    ``min(width, 128 // page)`` when both are powers of two."""
    return math.gcd(width, max(1, LANES // page))


def pages_fetched(first_page, seq_len, page: int, width: int, xp=jnp):
    """Table columns the kernel fetches and attends for one slot (or,
    elementwise, for arrays of slots): its live pages rounded up to
    whole blocks of :func:`block_pages`, and 0 for a slot whose table
    row starts at the null page.  ``xp`` is ``jnp`` inside the kernel
    and ``numpy`` for the server's host-side count."""
    per_block = block_pages(page, width)
    live = xp.minimum((seq_len + page - 1) // page, width)
    live = xp.where(first_page != 0, live, 0)
    return (live + per_block - 1) // per_block * per_block


def _kernel(page_table_ref, seq_lens_ref,      # scalar-prefetch refs
            q_ref, k_hbm, v_hbm, *rest,
            page: int, width: int, scale: float,
            has_extra: bool, has_scales: bool):
    it = iter(rest)
    ks_hbm, vs_hbm = (next(it), next(it)) if has_scales else (None, None)
    k0_ref, v0_ref = (next(it), next(it)) if has_extra else (None, None)
    o_ref, k_buf, v_buf = next(it), next(it), next(it)
    ks_buf, vs_buf = (next(it), next(it)) if has_scales else (None, None)
    sems, m_ref, l_ref, acc_ref = it
    hkv = q_ref.shape[1]
    per_block = block_pages(page, width)
    span = per_block * page                     # positions per block
    b = pl.program_id(0)
    seq_len = seq_lens_ref[b]
    n_blocks = pages_fetched(page_table_ref[b, 0], seq_len, page,
                             width) // per_block

    kv_bufs = [(k_hbm, k_buf), (v_hbm, v_buf)]
    scale_bufs = [(ks_hbm, ks_buf), (vs_hbm, vs_buf)] if has_scales else []

    def copies(blk, half):
        """The DMAs that bring block ``blk``'s pages into buffer half
        ``half``: one per page and pool, K and V each spread over their
        heads."""
        out = []
        for j in range(per_block):
            pid = page_table_ref[b, blk * per_block + j]
            out += [pltpu.make_async_copy(src.at[pid], dst.at[half, :, j],
                                          sems.at[half])
                    for src, dst in kv_bufs]
            out += [pltpu.make_async_copy(src.at[pid], dst.at[half, j],
                                          sems.at[half])
                    for src, dst in scale_bufs]
        return out

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def block(i, carry):
        half = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            for c in copies(i + 1, 1 - half):
                c.start()

        for c in copies(i, half):
            c.wait()
        q = q_ref[0]                                      # (Hkv, G, d)
        k = k_buf[half]                                   # (Hkv, blk, page, d)
        v = v_buf[half]
        if has_scales:
            # fused dequant: the quantized block is widened in VMEM and
            # its per-position scales multiply the (Hkv, G, span) scores
            # and probabilities — q.(s*k) == (q.k)*s — so full-precision
            # KV never exists outside this tile
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        k = k.reshape(hkv, span, k.shape[-1])
        v = v.reshape(hkv, span, v.shape[-1])
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if has_scales:
            s = s * _scale_rows(ks_buf, half, page, span)[:, None, :]
        pos = i * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=2, keepdims=True)
        if has_scales:
            p = p * _scale_rows(vs_buf, half, page, span)[:, None, :]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    for h in range(hkv):
        m_p, l_p, acc_p = m_ref[h], l_ref[h], acc_ref[h]
        if has_extra:
            # current token's (k, v): one more online-softmax column.
            # A slot with no live pages has m_p == NEG_INF, so alpha =
            # exp(NEG_INF - s0) == 0 and the column is all it attends.
            # extra_kv stays full precision (it is the CURRENT token,
            # never pooled).
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
        o_ref[0, h] = (acc_p / jnp.maximum(l_p, 1e-30)).astype(o_ref.dtype)


def _scale_rows(buf, slot, page: int, span: int) -> jax.Array:
    """``(Hkv, span)`` fp32 scales of one buffered block, each head's on
    its row and position-for-position with the score tile's lanes.
    Page ``j``'s scales arrive in lanes ``[0, page)`` of a 128-lane-
    padded row and are rotated to lanes ``[j * page, (j + 1) * page)``."""
    per_block = buf.shape[1]
    rows = buf[slot, 0].astype(jnp.float32)         # (Hkv, padded page)
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    for j in range(1, per_block):
        x = pltpu.roll(buf[slot, j].astype(jnp.float32), j * page, 1)
        rows = jnp.where(lane >= j * page, x, rows)
    return rows[:, :span]


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
    width = page_table.shape[1]
    if width < 1:
        raise ValueError("page_table must map at least one page per row")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    page = k_pages.shape[2]
    per_block = block_pages(page, width)
    has_extra = extra_kv is not None
    has_scales = k_scales is not None

    def per_seq(bb, pt, sl):
        return (bb, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, hkv, g, d), per_seq), hbm, hbm]
    inputs = [page_table, seq_lens, q, k_pages, v_pages]
    scratch = [pltpu.VMEM((2, hkv, per_block, page, d), k_pages.dtype),
               pltpu.VMEM((2, hkv, per_block, page, d), v_pages.dtype)]
    if has_scales:
        in_specs += [hbm, hbm]
        pad = -page % LANES             # whole 128-lane rows per page
        if pad:
            k_scales, v_scales = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
                                  for x in (k_scales, v_scales))
        scratch += [pltpu.VMEM((2, per_block, hkv, page + pad),
                               k_scales.dtype),
                    pltpu.VMEM((2, per_block, hkv, page + pad),
                               v_scales.dtype)]
        inputs += [k_scales, v_scales]
    if has_extra:
        in_specs += [
            pl.BlockSpec((1, hkv, d), lambda bb, pt, sl: (bb, 0, 0)),
        ] * 2
        inputs += [extra_kv[0], extra_kv[1]]
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, 1), jnp.float32),
                pltpu.VMEM((hkv, g, d), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), per_seq),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel, page=page, width=width,
                          scale=1.0 / math.sqrt(d), has_extra=has_extra,
                          has_scales=has_scales),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(*inputs)
