"""Shared neural layers: RMSNorm, RoPE, GQA attention (blocked/flash and
decode paths), SwiGLU MLP, embeddings.

All attention entry points take and return ``(batch, seq, heads, head_dim)``
tensors.  The prefill/train path is a *blocked online-softmax* (flash-style)
implementation in pure jnp — differentiable, O(S·block) memory — which also
serves as the oracle for the Pallas kernels in ``repro.kernels``.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.base import ModelConfig, dense_init, ones_init, zeros_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms & RoPE
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                  # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked flash attention (static shapes, differentiable)
# ---------------------------------------------------------------------------

def _block_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                window: int, kv_len: jax.Array | None) -> jax.Array:
    """(qb, kb) boolean mask of VALID positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    if kv_len is not None:
        m &= k_pos[None, :] < kv_len
    return m


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 512,
                    q_offset: int = 0) -> jax.Array:
    """Blocked online-softmax attention.

    q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd) with Hq % Hkv == 0.
    Memory is O(Sq·kv_block) per step instead of O(Sq·Sk).
    ``q_offset`` shifts query positions (for chunked prefill).
    """
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    groups = hq // hkv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    # pad seqs to block multiples
    pq = (-sq) % q_block
    pk = (-sk) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    nq, nk = qp.shape[1] // q_block, kp.shape[1] // kv_block

    # (nq, B, qb, Hkv, G, hd)
    qs = qp.reshape(b, nq, q_block, hkv, groups, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(b, nk, kv_block, hkv, hd)
    vs = vp.reshape(b, nk, kv_block, hkv, hd)
    scale = 1.0 / math.sqrt(hd)
    kv_valid = jnp.asarray(sk)

    def q_step(args):
        qi, q_blk = args
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, kj):
            o, m, l = carry
            k_blk = jax.lax.dynamic_index_in_dim(ks, kj, 1, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vs, kj, 1, keepdims=False)
            k_pos = kj * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqkgd,bnkd->bqkgn", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(q_pos, k_pos, causal=causal, window=window,
                               kv_len=kv_valid)        # (qb, kb)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bqkgn,bnkd->bqkgd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((b, q_block, hkv, groups, hd), jnp.float32)
        m0 = jnp.full((b, q_block, hkv, groups), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_block, hkv, groups), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0), jnp.arange(nk))
        return o / jnp.maximum(l[..., None], 1e-37)

    # Checkpoint per q-block: flash backward recomputes the kv scan from
    # (q, k, v) instead of storing per-kv-step probability blocks.
    out = jax.lax.map(jax.checkpoint(q_step), (jnp.arange(nq), qs))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * q_block, hq, hd)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_pos: jax.Array, *, window: int = 0,
                     extra_kv: tuple[jax.Array, jax.Array] | None = None,
                     ) -> jax.Array:
    """Single-token attention against a (B, Hkv, S, hd) cache.

    The head-major cache layout makes both dots layout-native (batch dims
    (b, h), contraction over the minor axis) — no transposed copies of the
    32k-token cache per layer (§Perf iteration A).

    ``extra_kv``: the CURRENT token's (k, v), each (B, Hkv, hd) — attended
    in addition to the cache, so the cache stays **read-only** inside the
    decode layer scan (its positions are masked strictly below cur_pos;
    the write happens once, batched over layers, after the scan).

    cur_pos: (B,) index of the token being generated (0-based).
    """
    b, hkv, sk, hd = k_cache.shape
    hq = q.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, hkv, groups, hd)  # Sq==1 squeezed
    s = jnp.einsum("bkgd,bknd->bkgn", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    pos = jnp.arange(sk)[None, :]                        # (1, S)
    if extra_kv is not None:
        valid = pos < cur_pos[:, None]     # cache: strictly past tokens
    else:
        valid = pos <= cur_pos[:, None]
    if window > 0:
        valid &= pos > (cur_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    if extra_kv is not None:
        k0, v0 = extra_kv
        s_self = jnp.einsum("bkgd,bkd->bkg", qg, k0,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.concatenate([s, s_self[..., None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if extra_kv is not None:
        p_cache, p_self = p[..., :-1], p[..., -1]
        o = jnp.einsum("bkgn,bknd->bkgd", p_cache.astype(v_cache.dtype),
                       v_cache, preferred_element_type=jnp.float32)
        o = o + p_self[..., None] * extra_kv[1][:, :, None, :].astype(
            jnp.float32)
    else:
        o = jnp.einsum("bkgn,bknd->bkgd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(b, 1, hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (params + apply)
# ---------------------------------------------------------------------------

def attn_params(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv_true = cfg.padded_heads, cfg.num_kv_heads
    hkv = cfg.padded_kv_heads
    wq = dense_init(ks[0], (d, hq * hd), cfg.dtype)
    if hq > cfg.num_heads:
        # zero the padded q-head slots so the padded model equals the true
        # architecture at init (wo rows zeroed below keeps them inert).
        mask = (jnp.arange(hq) < cfg.num_heads).repeat(hd)
        wq = wq * mask[None, :].astype(wq.dtype)
    # init true KV heads, tile to the padded/replicated count so the
    # architecture keeps its true number of distinct KV heads.  The
    # fan-in is d_model (the head axis is not contracted).
    wk1 = dense_init(ks[1], (d, hkv_true, hd), cfg.dtype,
                     scale=1.0 / math.sqrt(d))
    wv1 = dense_init(ks[2], (d, hkv_true, hd), cfg.dtype,
                     scale=1.0 / math.sqrt(d))
    reps = hkv // hkv_true if hkv % hkv_true == 0 else 0
    if reps:
        wk = jnp.tile(wk1, (1, reps, 1)).reshape(d, hkv * hd)
        wv = jnp.tile(wv1, (1, reps, 1)).reshape(d, hkv * hd)
    else:  # pad with fresh heads (e.g. 36 -> 48)
        extra = hkv - hkv_true
        wk = jnp.concatenate(
            [wk1, dense_init(ks[3], (d, extra, hd), cfg.dtype,
                             scale=1.0 / math.sqrt(d))],
            axis=1).reshape(d, hkv * hd)
        wv = jnp.concatenate(
            [wv1, dense_init(ks[4], (d, extra, hd), cfg.dtype,
                             scale=1.0 / math.sqrt(d))],
            axis=1).reshape(d, hkv * hd)
    wo = dense_init(ks[5], (hq * hd, d), cfg.dtype)
    if hq > cfg.num_heads:
        mask = (jnp.arange(hq) < cfg.num_heads).repeat(hd)
        wo = wo * mask[:, None].astype(wo.dtype)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def attn_specs(cfg: ModelConfig, *, cross: bool = False,
               stacked: bool = True) -> dict:
    L = (None,) if stacked else ()
    mk = lambda *dims: P(*L, *dims)
    p = {
        "wq": mk(None, "model"), "wk": mk(None, "model"),
        "wv": mk(None, "model"), "wo": mk("model", None),
    }
    if cfg.qkv_bias and not cross:
        p.update(bq=mk("model"), bk=mk("model"), bv=mk("model"))
    if cfg.qk_norm:
        p.update(q_norm=mk(None), k_norm=mk(None))
    return p


def _project_qkv(p: dict, x: jax.Array, x_kv: jax.Array, cfg: ModelConfig):
    b, s = x.shape[:2]
    skv = x_kv.shape[1]
    hq, hkv, hd = cfg.padded_heads, cfg.padded_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x_kv @ p["wk"]
    v = x_kv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, skv, hkv, hd)
    v = v.reshape(b, skv, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _heads_sharded(t: jax.Array) -> jax.Array:
    """Megatron-SP boundary: inside attention, tensors are
    (batch, FULL seq, sharded heads, hd).  Entering here from seq-sharded
    residuals lowers to one all-to-all per tensor instead of per-block
    resharding churn inside the flash loops."""
    from repro.runtime.sharding import maybe_constraint
    from repro.models.base import BATCH_AXES
    return maybe_constraint(t, P(BATCH_AXES, None, "model", None))


def _tp_gathered(t: jax.Array) -> jax.Array:
    """All-gather TP boundary for the SERVING path: replicate an
    activation (sharded heads or hidden dim) before an output projection
    against a *replicated* weight.

    An all-gather is pure data movement, and the full-width projection
    that follows runs the exact dot the single-device server runs — so
    sharded serving is **bit-identical** by construction.  The
    alternative (Megatron row-parallel: partial dots + all-reduce, kept
    for training where throughput beats determinism) rounds each
    shard's partial sum separately and flips greedy ties mid-stream.
    Outside a mesh this is a no-op."""
    from repro.runtime.sharding import replicate_constraint
    return replicate_constraint(t)


def attn_forward(p: dict, x: jax.Array, positions: jax.Array,
                 cfg: ModelConfig, *, causal: bool = True) -> jax.Array:
    """Full-sequence (train/prefill) self-attention; returns (B, S, d)."""
    q, k, v = _project_qkv(p, x, x, cfg)
    q = _heads_sharded(apply_rope(q, positions, cfg.rope_theta))
    k = _heads_sharded(apply_rope(k, positions, cfg.rope_theta))
    v = _heads_sharded(v)
    o = flash_attention(q, k, v, causal=causal, window=cfg.sliding_window,
                        q_block=cfg.q_block, kv_block=cfg.kv_block)
    b, s = x.shape[:2]
    return o.reshape(b, s, -1) @ p["wo"]


def _kv_roundtripped(k: jax.Array, v: jax.Array, cfg: ModelConfig):
    """The quantize->dequantize fixed point of (k, v) — exactly the
    values every later POOL read (shared-prefix gather, paged decode)
    dequantizes.  Quantized prefill attends these instead of the raw
    projections so a prefix-cached admission is bit-identical to an
    unshared one: both see the same round-tripped KV, whether it comes
    off the pool or is recomputed on the fly."""
    qdt, qmax = cfg.kv_pool_dtype(), cfg.kv_qmax()
    return (kv_dequantize(*kv_pool_quantize(k, qdt, qmax), k.dtype),
            kv_dequantize(*kv_pool_quantize(v, qdt, qmax), v.dtype))


def attn_prefill_kv(p: dict, x: jax.Array, positions: jax.Array,
                    cfg: ModelConfig, *, kv_roundtrip: bool = False):
    """Like attn_forward but also returns (k, v) for cache seeding.
    Serving path: the head axis is gathered before the out projection
    (all-gather TP — see :func:`_tp_gathered`).  ``kv_roundtrip``
    (quantized page pools) attends the quantize->dequantize round trip
    of K/V while still returning the raw projections for the pool
    write — scattering quantizes them to the very bytes the round trip
    came from."""
    q, k, v = _project_qkv(p, x, x, cfg)
    q = _heads_sharded(apply_rope(q, positions, cfg.rope_theta))
    k = _heads_sharded(apply_rope(k, positions, cfg.rope_theta))
    v = _heads_sharded(v)
    ka, va = _kv_roundtripped(k, v, cfg) if kv_roundtrip else (k, v)
    o = _tp_gathered(
        flash_attention(q, ka, va, causal=True, window=cfg.sliding_window,
                        q_block=cfg.q_block, kv_block=cfg.kv_block))
    b, s = x.shape[:2]
    return o.reshape(b, s, -1) @ p["wo"], (k, v)


def attn_prefill_prefix_kv(p: dict, x: jax.Array, positions: jax.Array,
                           k_prefix: jax.Array, v_prefix: jax.Array,
                           cfg: ModelConfig, *,
                           kv_roundtrip: bool = False):
    """Prefill attention for a prompt SUFFIX against a cached prefix.

    x: (B, S_new, d) hidden states of the suffix chunk only; positions:
    (S_new,) absolute positions (prefix_len + arange); k_prefix/v_prefix:
    (B, prefix_len, Hkv, hd) the shared prefix KV in attention layout
    (gathered from the page pool).  Computes exactly the suffix rows of
    the full-prompt flash attention: the concatenated K/V equal what a
    full prefill would have projected (causality makes prefix KV
    independent of the suffix, and the pool stores K/V in the same dtype
    attention consumes), Sk and hence the kv blocking match the full
    prompt, and ``q_offset`` shifts the causal mask — so suffix hidden
    states, and therefore the sampled tokens downstream, are
    bit-identical to an unshared prefill.  Returns
    (out (B, S_new, d), (k_new, v_new) for the pool write).
    """
    q, k, v = _project_qkv(p, x, x, cfg)
    q = _heads_sharded(apply_rope(q, positions, cfg.rope_theta))
    k = _heads_sharded(apply_rope(k, positions, cfg.rope_theta))
    v = _heads_sharded(v)
    # quantized pools: the gathered prefix is already the round-tripped
    # values; round-trip the suffix too so the concatenated KV equals a
    # full quantized prefill's (bit-identity across shared/unshared)
    ka, va = _kv_roundtripped(k, v, cfg) if kv_roundtrip else (k, v)
    prefix_len = k_prefix.shape[1]
    kf = jnp.concatenate([k_prefix.astype(k.dtype), ka], axis=1)
    vf = jnp.concatenate([v_prefix.astype(v.dtype), va], axis=1)
    o = _tp_gathered(
        flash_attention(q, kf, vf, causal=True, window=cfg.sliding_window,
                        q_block=cfg.q_block, kv_block=cfg.kv_block,
                        q_offset=prefix_len))
    b, s = x.shape[:2]
    return o.reshape(b, s, -1) @ p["wo"], (k, v)


def attn_decode(p: dict, x: jax.Array, cache_k: jax.Array,
                cache_v: jax.Array, cur_pos: jax.Array, cfg: ModelConfig):
    """One-token self-attention.  The cache is READ-ONLY here: the current
    token's (k, v) are attended via the extra_kv path and returned for a
    single post-scan batched write (§Perf iteration A').

    x: (B, 1, d); cache_[kv]: (B, Hkv, S, hd); cur_pos: (B,) position.
    Returns (out (B,1,d), k_new (B,Hkv,hd), v_new (B,Hkv,hd)).
    """
    q, k, v = _project_qkv(p, x, x, cfg)
    pos = cur_pos[:, None]                               # (B,1)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    b = x.shape[0]
    k0 = k[:, 0]                                         # (B, Hkv, hd)
    v0 = v[:, 0]
    if cfg.sliding_window > 0 and cache_k.shape[2] <= cfg.sliding_window:
        # rolling window cache (rotated slots): mask strictly-past written
        # slots; current token joins via extra_kv.
        o = _decode_window_rotated(q, cache_k, cache_v, cur_pos,
                                   cfg.sliding_window, extra_kv=(k0, v0))
    else:
        o = decode_attention(q, cache_k, cache_v, cur_pos,
                             window=cfg.sliding_window, extra_kv=(k0, v0))
    out = _tp_gathered(o).reshape(b, 1, -1) @ p["wo"]
    return out, k0, v0


def _decode_window_rotated(q, k_cache, v_cache, cur_pos, window,
                           extra_kv=None):
    """Attention over a rotated rolling-window cache (no RoPE re-rotation
    needed because keys were rotated at write time with absolute phase).
    Cache layout (B, Hkv, W, hd).

    With ``extra_kv`` the cache is READ-ONLY: slot (cur_pos % W) still
    holds the stale position cur_pos - W (outside the window) and is
    masked; the current token's fresh (k, v) join via the extra column.
    """
    b, hkv, w, hd = k_cache.shape
    hq = q.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, hkv, groups, hd)
    s = jnp.einsum("bkgd,bknd->bkgn", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    # slot n holds the largest written position p == n (mod W); with the
    # current token unwritten that p is < cur_pos and within the window
    # except for the own slot (exactly W back).
    slots = jnp.arange(w)[None, :]
    if extra_kv is not None:
        valid = slots < cur_pos[:, None]
        valid &= slots != (cur_pos % w)[:, None]
    else:
        valid = slots <= cur_pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    if extra_kv is not None:
        k0, v0 = extra_kv
        s_self = jnp.einsum("bkgd,bkd->bkg", qg, k0,
                            preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.concatenate([s, s_self[..., None]], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgn,bknd->bkgd",
                       p[..., :-1].astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        o = o + p[..., -1][..., None] * v0[:, :, None, :].astype(jnp.float32)
    else:
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgn,bknd->bkgd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
    return o.reshape(b, 1, hq, hd).astype(q.dtype)


def to_cache_layout(k: jax.Array) -> jax.Array:
    """(B, S, H, hd) attention layout -> (B, H, S, hd) cache layout."""
    return k.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Block-pool paged decode attention (FengHuang KV paging in the serving hot
# path).  The pool holds strictly-past tokens; the current token's (k, v)
# joins as an extra softmax column so the pool stays read-only inside the
# decode layer scan, exactly like the dense extra_kv path above.
# ---------------------------------------------------------------------------

def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           cur_pos: jax.Array,
                           extra_kv: tuple[jax.Array, jax.Array], *,
                           k_scales: jax.Array | None = None,
                           v_scales: jax.Array | None = None,
                           use_kernel: bool | None = None) -> jax.Array:
    """Single-token attention against a (P, Hkv, page, hd) page pool.

    q: (B, 1, Hq, hd); page_table: (B, n_pages) int32 (null-page padded);
    cur_pos: (B,) — pooled positions < cur_pos are live, the current
    token arrives via ``extra_kv``.  ``k_scales``/``v_scales``
    ((P, Hkv, page), quantized pools only) dequantize inline: the kernel
    rescales each block's score tile inside its online-softmax loop; the
    fallback rescales the gathered fp32 view — full-precision KV never
    materializes pool-wide either way.  Routed once per backend: the
    Pallas ``paged_attention`` kernel on TPU (scalar-prefetched page
    tables, one call per head shard under a mesh), the gather +
    :func:`decode_attention` composition elsewhere.
    """
    from repro.kernels.paged_attention import ops as paged_ops

    b, _, hq, hd = q.shape
    if use_kernel is None:
        use_kernel = paged_ops.use_pallas_kernel()
    if use_kernel:
        hkv = k_pages.shape[1]
        qg = q.reshape(b, hkv, hq // hkv, hd)
        o = paged_ops.sharded_paged_attention(
            qg, k_pages, v_pages, page_table, cur_pos, extra_kv=extra_kv,
            k_scales=k_scales, v_scales=v_scales)
        return o.reshape(b, 1, hq, hd).astype(q.dtype)
    # spec-threaded gather: each device gathers only its "model" head
    # shard of the mapped pages, so tensor-parallel paged decode reads
    # stay collective-free (see ops.GATHERED_KV_SPEC)
    k = paged_ops.gather_pages_sharded(k_pages, page_table)
    v = paged_ops.gather_pages_sharded(v_pages, page_table)
    if k_scales is not None:
        from repro.kernels.paged_attention.ref import to_f32
        ks = paged_ops.gather_scales_sharded(k_scales, page_table)
        vs = paged_ops.gather_scales_sharded(v_scales, page_table)
        # to_f32 dequantizes fp8 through the convert LUT (bit-identical
        # to astype; ~8x faster on CPU — see ref.gatherable_view)
        k = to_f32(k) * ks.astype(jnp.float32)[..., None]
        v = to_f32(v) * vs.astype(jnp.float32)[..., None]
    return decode_attention(q, k, v, cur_pos, extra_kv=extra_kv)


def attn_decode_paged(p: dict, x: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, page_table: jax.Array,
                      cur_pos: jax.Array, cfg: ModelConfig,
                      k_scales: jax.Array | None = None,
                      v_scales: jax.Array | None = None):
    """One-token self-attention over this layer's page pool (read-only —
    the (k, v) returned are written post-scan in one batched scatter).

    x: (B, 1, d); [kv]_pages: (P, Hkv, page, hd); page_table: (B, n);
    cur_pos: (B,); [kv]_scales: (P, Hkv, page) dequant scales when the
    pool is quantized.  Returns (out (B,1,d), k0 (B,Hkv,hd), v0
    (B,Hkv,hd)) — k0/v0 full precision; the post-scan scatter quantizes.
    """
    q, k, v = _project_qkv(p, x, x, cfg)
    pos = cur_pos[:, None]                               # (B, 1)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    b = x.shape[0]
    k0 = k[:, 0]                                         # (B, Hkv, hd)
    v0 = v[:, 0]
    o = paged_decode_attention(q, k_pages, v_pages, page_table, cur_pos,
                               (k0, v0), k_scales=k_scales,
                               v_scales=v_scales)
    out = _tp_gathered(o).reshape(b, 1, -1) @ p["wo"]
    return out, k0, v0


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (§Perf iteration A3): per-token-per-head
# absmax scales halve the decode memory term's KV component (the dominant
# term for batch-128 decode).
# ---------------------------------------------------------------------------

def kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (..., hd) -> (int8 values, scale (...,) bf16)."""
    return kv_pool_quantize(x, jnp.int8, 127.0)


def kv_pool_quantize(x: jax.Array, qdtype,
                     qmax: float) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(..., head)-vector absmax quantization shared by the
    int8 (qmax=127) and fp8_e4m3 (qmax=448) page pools.

    x: (..., hd) -> (``qdtype`` values, scale (...,) bf16).  The scale is
    computed from its own bf16 storage value so a write/read round trip
    reproduces exactly what the attention read path dequantizes — the
    invariant the quantized-vs-quantized bit-identity contract rests on.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-8).astype(jnp.bfloat16)
    y = x.astype(jnp.float32) / scale.astype(jnp.float32)[..., None]
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        y = jnp.round(y)
    q = jnp.clip(y, -qmax, qmax).astype(qdtype)
    return q, scale


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    from repro.kernels.paged_attention.ref import to_f32
    return (to_f32(q) * scale.astype(jnp.float32)[..., None]).astype(dtype)


def cross_attn_forward(p: dict, x: jax.Array, enc_kv: tuple[jax.Array, jax.Array],
                       cfg: ModelConfig) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V."""
    b, s = x.shape[:2]
    hq, hd = cfg.padded_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k, v = enc_kv
    o = flash_attention(q, k, v, causal=False, q_block=cfg.q_block,
                        kv_block=cfg.kv_block)
    return o.reshape(b, s, -1) @ p["wo"]


def cross_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig):
    b, s = enc_out.shape[:2]
    hkv, hd = cfg.padded_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(b, s, hkv, hd)
    v = (enc_out @ p["wv"]).reshape(b, s, hkv, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLP / embeddings
# ---------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, (cfg.d_model, d_ff), cfg.dtype),
        "wg": dense_init(k2, (cfg.d_model, d_ff), cfg.dtype),
        "wo": dense_init(k3, (d_ff, cfg.d_model), cfg.dtype),
    }


def mlp_specs(stacked: bool = True) -> dict:
    L = (None,) if stacked else ()
    return {"wi": P(*L, None, "model"), "wg": P(*L, None, "model"),
            "wo": P(*L, "model", None)}


def mlp_forward(p: dict, x: jax.Array, *, gather_tp: bool = False
                ) -> jax.Array:
    """``gather_tp`` (serving): gather the d_ff-sharded hidden before
    the down projection so the full-width dot is bit-identical to
    single-device (the weight is replicated in the serving placement);
    training keeps the Megatron partial-sum + reduce-scatter path."""
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    if gather_tp:
        h = _tp_gathered(h)
    return h @ p["wo"]


def mlp2_params(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    """Non-gated GELU MLP (whisper-style)."""
    d_ff = d_ff or cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {"wi": dense_init(k1, (cfg.d_model, d_ff), cfg.dtype),
            "wo": dense_init(k2, (d_ff, cfg.d_model), cfg.dtype)}


def mlp2_specs(stacked: bool = True) -> dict:
    L = (None,) if stacked else ()
    return {"wi": P(*L, None, "model"), "wo": P(*L, "model", None)}


def mlp2_forward(p: dict, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ p["wi"]) @ p["wo"]


def embed_params(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"tok": dense_init(k1, (cfg.padded_vocab, cfg.d_model), cfg.dtype,
                           scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, (cfg.d_model, cfg.padded_vocab), cfg.dtype)
    return p


def embed_specs(cfg: ModelConfig) -> dict:
    p = {"tok": P("model", None)}
    if not cfg.tie_embeddings:
        p["head"] = P(None, "model")
    return p


def embed_lookup(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def lm_head(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ p["tok"].T
    return x @ p["head"]
