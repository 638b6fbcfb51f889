"""Dense decoder-only transformer (qwen2.5 / qwen3 / minicpm / starcoder2,
and the llava backbone) with FengHuang paging as a first-class option.

Layers are stacked on a leading L axis and executed with
:func:`repro.memory.orchestrator.paged_scan`, so the same model
definition runs shared-nothing (weights resident in HBM) or
FengHuang-paged (weights and optionally KV in the remote tier,
double-buffered prefetch).  Every model owns a
:class:`repro.memory.MemoryOrchestrator` (``self.mem``) planned from its
config's pager policy; all layer scans route through it.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.memory import MemoryOrchestrator
from repro.models import layers as L
from repro.models.base import (ModelConfig, BATCH_AXES, DecodeState,
                               split_keys)
from repro.runtime.sharding import SEQ_SHARDED_ACTS, maybe_constraint


def _scatter_pages(cache: dict, pages: jax.Array, k_new: jax.Array,
                   v_new: jax.Array, cfg: ModelConfig) -> dict:
    """Write (L, B, S, Hkv, hd) prompt KV into the page pools: ONE
    scatter per pool covering every layer, page and head.  ``pages``:
    (B, n) page ids with n * page >= S; KV positions start at the first
    mapped page's base, extra positions receive only padding (written —
    so a freshly filled page is valid in its entirety — but masked by
    seq_lens on every read).  Quantized pools (``cfg.kv_dtype``)
    quantize on write: per-(position, head) absmax scales land in the
    ``k_scale``/``v_scale`` arrays with the same scatter pattern, so a
    page's bytes are a pure function of the tokens it covers (the
    prefix-sharing contract)."""
    page = cache["k_pages"].shape[3]
    n = pages.shape[1]
    seq = k_new.shape[2]
    pad = n * page - seq
    if pad < 0:
        raise ValueError(f"page table maps {n * page} positions but the "
                         f"prompt chunk has {seq}")

    def scatter(pool, val, spec):
        # (L, B, S, Hkv, ...) -> (L, B, n, Hkv, page, ...), one scatter
        val = jnp.pad(val, ((0, 0), (0, 0), (0, pad))
                      + ((0, 0),) * (val.ndim - 3))
        l_, b_ = val.shape[:2]
        val = val.reshape((l_, b_, n, page) + val.shape[3:])
        val = jnp.swapaxes(val, 3, 4)
        # under a mesh the update's head axis matches the pool's shard
        # layout, so the scatter stays device-local per head shard
        val = maybe_constraint(val, spec)
        return pool.at[:, pages].set(val.astype(pool.dtype))

    kv_spec = P(None, None, None, "model", None, None)
    if cfg.kv_quantized:
        qdt, qmax = cfg.kv_pool_dtype(), cfg.kv_qmax()
        k_new, ks = L.kv_pool_quantize(k_new, qdt, qmax)
        v_new, vs = L.kv_pool_quantize(v_new, qdt, qmax)
        sc_spec = P(None, None, None, "model", None)
        return {"k_pages": scatter(cache["k_pages"], k_new, kv_spec),
                "v_pages": scatter(cache["v_pages"], v_new, kv_spec),
                "k_scale": scatter(cache["k_scale"], ks, sc_spec),
                "v_scale": scatter(cache["v_scale"], vs, sc_spec)}
    return {"k_pages": scatter(cache["k_pages"], k_new, kv_spec),
            "v_pages": scatter(cache["v_pages"], v_new, kv_spec)}


class DenseLM:
    """Decoder-only LM.  Also the base class for the MoE and VLM variants."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.mem = MemoryOrchestrator.plan(cfg)

    # ----- params -----------------------------------------------------------
    def init_layer(self, key) -> dict:
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn": L.attn_params(k1, cfg),
            "mlp": L.mlp_params(k2, cfg),
            "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
            "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        }

    def layer_specs(self) -> dict:
        return {
            "attn": L.attn_specs(self.cfg),
            "mlp": L.mlp_specs(),
            "ln1": P(None, None), "ln2": P(None, None),
        }

    def init(self, key) -> dict:
        cfg = self.cfg
        ke, kl = jax.random.split(key)
        layer_keys = split_keys(kl, cfg.num_layers)
        stacked = jax.vmap(self.init_layer)(jnp.stack(layer_keys))
        return {
            "embed": L.embed_params(ke, cfg),
            "layers": stacked,
            "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        }

    def param_specs(self) -> dict:
        return {
            "embed": L.embed_specs(self.cfg),
            "layers": self.layer_specs(),
            "ln_f": P(None),
        }

    def serving_param_specs(self) -> dict:
        """``param_specs`` with the contraction-sharded output
        projections (``wo`` of attention and the MLP) replicated: the
        serving blocks all-gather their activations before these dots
        (:func:`repro.models.layers._tp_gathered`), so the full-width
        projection is bitwise identical to single-device — the placement
        and the constraint are two halves of one contract.  Everything
        else (QKV, gate/up, embeddings, LM head) keeps its model-axis
        shard."""
        def fix(path, s):
            key = jax.tree_util.keystr(path)
            # expert banks (['moe']['wo']) are expert-axis sharded, not
            # contraction-sharded — replicating them would multiply
            # per-device expert memory for no determinism gain
            if key.endswith("['wo']") and "['moe']" not in key:
                return P(*(None,) * len(s))
            return s
        return jax.tree_util.tree_map_with_path(
            fix, self.param_specs(),
            is_leaf=lambda x: isinstance(x, P))

    # ----- blocks ------------------------------------------------------------
    def ffn(self, lp: dict, x: jax.Array, *,
            gather_tp: bool = False) -> jax.Array:
        return L.mlp_forward(lp["mlp"], x, gather_tp=gather_tp)

    def block_train(self, lp: dict, x: jax.Array,
                    positions: jax.Array) -> jax.Array:
        cfg = self.cfg
        # constraining each sub-block's output to seq-sharded turns the
        # TP partial-sum into a reduce-scatter (half the wire of
        # all-reduce) — Megatron-SP proper (§Perf iteration C).
        a = maybe_constraint(
            L.attn_forward(lp["attn"], L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
                           positions, cfg), SEQ_SHARDED_ACTS)
        h = x + a
        f = maybe_constraint(
            self.ffn(lp, L.rmsnorm(h, lp["ln2"], cfg.norm_eps)),
            SEQ_SHARDED_ACTS)
        return h + f

    def block_prefill(self, lp: dict, x: jax.Array, positions: jax.Array,
                      kv_roundtrip: bool = False):
        cfg = self.cfg
        a, kv = L.attn_prefill_kv(lp["attn"],
                                  L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
                                  positions, cfg, kv_roundtrip=kv_roundtrip)
        h = x + a
        return h + self.ffn(lp, L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                            gather_tp=True), kv

    def block_decode(self, lp: dict, x: jax.Array, ck, cv, cur_pos):
        """Cache is read-only; returns the current token's (k, v) for the
        single post-scan batched write."""
        cfg = self.cfg
        a, k0, v0 = L.attn_decode(lp["attn"],
                                  L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
                                  ck, cv, cur_pos, cfg)
        h = x + a
        return h + self.ffn(lp, L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                            gather_tp=True), k0, v0

    def block_prefill_prefix(self, lp: dict, x: jax.Array,
                             positions: jax.Array, k_prefix, v_prefix,
                             kv_roundtrip: bool = False):
        """block_prefill for a prompt suffix whose prefix KV already
        lives in the page pool (prefix-cached admission)."""
        cfg = self.cfg
        a, kv = L.attn_prefill_prefix_kv(
            lp["attn"], L.rmsnorm(x, lp["ln1"], cfg.norm_eps), positions,
            k_prefix, v_prefix, cfg, kv_roundtrip=kv_roundtrip)
        h = x + a
        return h + self.ffn(lp, L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                            gather_tp=True), kv

    def block_decode_paged(self, lp: dict, x: jax.Array, k_pages, v_pages,
                           pages, cur_pos, k_scales=None, v_scales=None):
        """block_decode against this layer's page pool (also read-only);
        ``k_scales``/``v_scales`` carry a quantized pool's per-slot
        dequant scales into the fused attention read."""
        cfg = self.cfg
        a, k0, v0 = L.attn_decode_paged(lp["attn"],
                                        L.rmsnorm(x, lp["ln1"], cfg.norm_eps),
                                        k_pages, v_pages, pages, cur_pos, cfg,
                                        k_scales=k_scales, v_scales=v_scales)
        h = x + a
        return h + self.ffn(lp, L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                            gather_tp=True), k0, v0

    # ----- forward passes ----------------------------------------------------
    def _embed(self, params, tokens):
        return L.embed_lookup(params["embed"], tokens)

    def forward_hidden(self, params: dict, tokens: jax.Array,
                       extra: dict | None = None) -> jax.Array:
        """Full-sequence forward without the LM head (chunked-loss path)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if extra and "patches" in extra:   # VLM: prepend patch embeddings
            x = jnp.concatenate([extra["patches"].astype(x.dtype), x], axis=1)
        positions = jnp.arange(x.shape[1])

        def body(h, lp):
            # Megatron-style sequence parallelism: the residual saved per
            # layer for backward is seq-sharded over the model axis.
            h = maybe_constraint(h, SEQ_SHARDED_ACTS)
            fn = self.block_train
            if cfg.remat:
                fn = jax.checkpoint(fn)
            return fn(lp, h, positions), None

        x, _ = self.mem.layer_scan(body, x, params["layers"])
        return L.rmsnorm(x, params["ln_f"], cfg.norm_eps)

    def forward(self, params: dict, tokens: jax.Array,
                extra: dict | None = None) -> jax.Array:
        """Training/eval forward over a full sequence -> logits (B, S, V)."""
        x = self.forward_hidden(params, tokens, extra)
        return L.lm_head(params["embed"], x, self.cfg)

    # ----- KV cache -----------------------------------------------------------
    def cache_seq(self, max_seq: int) -> int:
        w = self.cfg.sliding_window
        return min(max_seq, w) if w > 0 else max_seq

    def init_cache(self, batch: int, max_seq: int) -> dict:
        cfg = self.cfg
        s = self.cache_seq(max_seq)
        # head-major layout (L, B, Hkv, S, hd): decode dots are
        # layout-native (no transposed cache copies) — §Perf iteration A.
        shape = (cfg.num_layers, batch, cfg.padded_kv_heads, s, cfg.head_dim)
        if cfg.kv_quant:
            # int8 values + per-token-per-head bf16 absmax scales (A3)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
                    "v_scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def cache_specs(self) -> dict:
        spec = P(None, BATCH_AXES, "model", None, None)
        if self.cfg.kv_quant:
            sc = P(None, BATCH_AXES, "model", None)
            return {"k": spec, "v": spec, "k_scale": sc, "v_scale": sc}
        return {"k": spec, "v": spec}

    # ----- block-pool paged KV cache ----------------------------------------
    def supports_paged_kv(self) -> bool:
        """Block-pool KV covers full causal attention in bf16/fp32;
        rolling-window and int8 caches keep the dense per-slot layout."""
        return self.cfg.sliding_window == 0 and not self.cfg.kv_quant

    def init_paged_cache(self, num_pages: int,
                         page_size: int | None = None) -> dict:
        """Stacked multi-layer page pools, (L, P, Hkv, page, hd): one page
        holds every KV head, so the decode kernel DMAs a page as one
        contiguous block.  Page 0 is the null page (never allocated;
        absorbs idle-slot writes).

        With ``cfg.kv_dtype`` set the pools hold int8 / fp8 values and
        per-(page, head, slot) bf16 absmax scales ride alongside in
        ``k_scale``/``v_scale`` (L, P, Hkv, page) — dequant is fused into
        every pool read, so full-precision KV never materializes."""
        cfg = self.cfg
        if not self.supports_paged_kv():
            raise ValueError(
                "paged KV cache requires sliding_window == 0 and "
                "kv_quant == False")
        page = page_size or cfg.page_size
        shape = (cfg.num_layers, num_pages, cfg.padded_kv_heads, page,
                 cfg.head_dim)
        pool_dt = cfg.kv_pool_dtype()
        cache = {"k_pages": jnp.zeros(shape, pool_dt),
                 "v_pages": jnp.zeros(shape, pool_dt)}
        if cfg.kv_quantized:
            cache["k_scale"] = jnp.zeros(shape[:-1], jnp.bfloat16)
            cache["v_scale"] = jnp.zeros(shape[:-1], jnp.bfloat16)
        return cache

    def paged_cache_specs(self) -> dict:
        from repro.kernels.paged_attention.ops import (STACKED_POOL_SPEC,
                                                       STACKED_SCALE_SPEC)
        specs = {"k_pages": STACKED_POOL_SPEC, "v_pages": STACKED_POOL_SPEC}
        if self.cfg.kv_quantized:
            # scales shard on the head axis exactly like their pools
            specs["k_scale"] = STACKED_SCALE_SPEC
            specs["v_scale"] = STACKED_SCALE_SPEC
        return specs

    def prefill(self, params: dict, tokens: jax.Array, cache: dict,
                extra: dict | None = None):
        """Process the prompt, fill the cache, return last-position logits."""
        cfg = self.cfg
        cache = self.mem.kv_in(cache)
        x = self._embed(params, tokens)
        if extra and "patches" in extra:
            x = jnp.concatenate([extra["patches"].astype(x.dtype), x], axis=1)
        seq = x.shape[1]
        positions = jnp.arange(seq)
        cs = self.cache_seq(cache["k"].shape[3])

        def body(h, lp):
            h, (k, v) = self.block_prefill(lp, h, positions)
            return h, (L.to_cache_layout(k[:, -cs:]),
                       L.to_cache_layout(v[:, -cs:]))

        x, kv = self.mem.layer_scan(body, x, params["layers"])
        k_new, v_new = kv
        if cfg.sliding_window > 0 and cs == cfg.sliding_window:
            # rolling cache: position p lives at slot p % W.  The last cs
            # keys cover positions seq-cs .. seq-1, so rotate them into
            # place: slot((seq-cs)+i) = (seq % W + i) % W.
            shift = seq % cs
            k_new = jnp.roll(k_new, shift, axis=3)
            v_new = jnp.roll(v_new, shift, axis=3)
        if cfg.kv_quant:
            kq, ks = L.kv_quantize(k_new)
            vq, vs = L.kv_quantize(v_new)
            upd = lambda buf, val, ax: jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), 0, axis=ax)
            cache = {"k": upd(cache["k"], kq, 3),
                     "v": upd(cache["v"], vq, 3),
                     "k_scale": upd(cache["k_scale"], ks, 3),
                     "v_scale": upd(cache["v_scale"], vs, 3)}
        else:
            cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k_new.astype(cache["k"].dtype), 0, axis=3),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v_new.astype(cache["v"].dtype), 0, axis=3),
            }
        x = L.rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        return L.lm_head(params["embed"], x, cfg), cache

    def prefill_paged(self, params: dict, tokens: jax.Array, cache: dict,
                      pages: jax.Array, extra: dict | None = None):
        """Prefill the prompt straight into freshly allocated pages.

        tokens: (B, S); pages: (B, n) page ids with n * page >= S (extra
        columns may map the null page — they receive only padding).  The
        whole prompt's KV lands in the pools with ONE scatter per pool
        covering every layer, page and head — no dense staging buffer, no
        per-slot splice.  Returns (last-position logits, cache).
        """
        cfg = self.cfg
        cache = self.mem.kv_in(cache)
        x = self._embed(params, tokens)
        if extra and "patches" in extra:
            x = jnp.concatenate([extra["patches"].astype(x.dtype), x], axis=1)
        seq = x.shape[1]
        positions = jnp.arange(seq)
        quant = cfg.kv_quantized

        def body(h, lp):
            # keep (B, S, Hkv, hd) attention layout: the page reshape
            # below wants seq-major.  Quantized pools attend the
            # quantize->dequantize round trip of the fresh KV — the same
            # values any pool read dequantizes — so a prefix-shared
            # admission is bit-identical to this unshared one.
            return self.block_prefill(lp, h, positions, kv_roundtrip=quant)

        x, (k_new, v_new) = self.mem.layer_scan(body, x, params["layers"])
        cache = _scatter_pages(cache, pages, k_new, v_new, cfg)
        x = L.rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        return L.lm_head(params["embed"], x, cfg), cache

    def prefill_paged_prefix(self, params: dict, tokens: jax.Array,
                             cache: dict, prefix_pages: jax.Array,
                             pages: jax.Array):
        """Prefill only the prompt SUFFIX against a pool-resident shared
        prefix (prefix-cached admission).

        tokens: (B, S_new) suffix tokens starting at position
        ``prefix_pages.shape[1] * page`` (shared prefixes are whole
        pages, so the suffix always begins on a page boundary);
        prefix_pages: (B, n_pre) fully-shared page ids whose KV is read,
        never written; pages: (B, n_new) freshly allocated pages that
        receive the suffix KV.  Per-layer FLOPs scale with the suffix
        length — the prefix contributes only the attention reads — and
        the suffix hidden states are bit-identical to a full unshared
        prefill (see :func:`repro.models.layers.attn_prefill_prefix_kv`).
        Quantized pools dequantize the gathered prefix through its
        stored scales, and an unshared :meth:`prefill_paged` attends the
        same quantize->dequantize round trip of its fresh KV, so the
        bit-identity guarantee holds for quantized pools too: sharing or
        not sharing a prefix never changes a token.
        Returns (last-position logits, cache).
        """
        from repro.kernels.paged_attention.ops import (gather_pages_sharded,
                                                       gather_scales_sharded)

        cfg = self.cfg
        cache = self.mem.kv_in(cache)
        x = self._embed(params, tokens)
        seq = x.shape[1]
        page = cache["k_pages"].shape[3]
        prefix_len = prefix_pages.shape[1] * page
        positions = prefix_len + jnp.arange(seq)
        quant = cfg.kv_quantized

        def body(h, lp, cl):
            if quant:
                kp, vp, ksc, vsc = cl
            else:
                kp, vp = cl
            # (B, Hkv, pre, hd) cache layout
            kpre = gather_pages_sharded(kp, prefix_pages)
            vpre = gather_pages_sharded(vp, prefix_pages)
            if quant:
                ks = gather_scales_sharded(ksc, prefix_pages)  # (B, Hkv, pre)
                vs = gather_scales_sharded(vsc, prefix_pages)
                kpre = L.kv_dequantize(kpre, ks, cfg.dtype)
                vpre = L.kv_dequantize(vpre, vs, cfg.dtype)
            # -> (B, pre, Hkv, hd) attention layout
            kpre = kpre.transpose(0, 2, 1, 3)
            vpre = vpre.transpose(0, 2, 1, 3)
            return self.block_prefill_prefix(lp, h, positions, kpre, vpre,
                                             kv_roundtrip=quant)

        xs = ((cache["k_pages"], cache["v_pages"],
               cache["k_scale"], cache["v_scale"]) if quant
              else (cache["k_pages"], cache["v_pages"]))
        x, (k_new, v_new) = self.mem.layer_scan(body, x, params["layers"],
                                                xs=xs)
        cache = _scatter_pages(cache, pages, k_new, v_new, cfg)
        x = L.rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        return L.lm_head(params["embed"], x, cfg), cache

    def prefill_paged_chunk(self, params: dict, tokens: jax.Array,
                            cache: dict, done_pages: jax.Array,
                            pages: jax.Array):
        """Continue a CHUNKED prefill: process the next page-aligned
        slice of the prompt against the request's own earlier chunks.

        tokens: (B, S_chunk) prompt slice starting at position
        ``done_pages.shape[1] * page``; done_pages: (B, n_done) pages
        already filled by prior chunks of the SAME request; pages:
        (B, n_new) fresh pages for this chunk.  This is exactly
        :meth:`prefill_paged_prefix` with the "prefix" being the
        request's own completed chunks instead of a shared prompt
        prefix — same gather-dequant read of pool-resident KV, same
        kv-roundtrip attention, so a prompt prefilled in page-aligned
        chunks is **bit-identical** (logits and pool bytes) to one
        monolithic :meth:`prefill_paged`.  The async prefill engine
        (``repro.runtime.prefill``) leans on this to bound the work a
        single dispatch injects ahead of decode.
        Returns (last-position logits, cache).
        """
        return self.prefill_paged_prefix(params, tokens, cache,
                                         done_pages, pages)

    def decode_step(self, params: dict, tokens: jax.Array, cache: dict,
                    cur_pos: jax.Array, extra: dict | None = None,
                    pages: jax.Array | None = None):
        """tokens: (B, 1); cur_pos: (B,) absolute position being written;
        pages: (B, n_pages) block-pool page table (None = dense cache)."""
        cfg = self.cfg
        cache = self.mem.kv_in(cache)
        x = self._embed(params, tokens)
        if pages is not None:
            x, cache = self._decode_pool(params, x, cache, cur_pos, pages)
        elif cfg.pager.offload_kv and not cfg.kv_quant:
            x, cache = self._decode_paged_cache(params, x, cache, cur_pos)
        else:
            x, cache = self._decode_scatter(params, x, cache, cur_pos)
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
        return L.lm_head(params["embed"], x, cfg), cache

    def _cache_slot(self, cache_seq: int, cur_pos: jax.Array) -> jax.Array:
        w = self.cfg.sliding_window
        return (cur_pos % cache_seq) if (w > 0 and cache_seq <= w) else cur_pos

    def _decode_scatter(self, params: dict, x: jax.Array, cache: dict,
                        cur_pos: jax.Array):
        cfg = self.cfg
        b = x.shape[0]

        def body(h, lp, cache_layer):
            if cfg.kv_quant:
                ck, cv, ks, vs = cache_layer
                ck = L.kv_dequantize(ck, ks, cfg.dtype)
                cv = L.kv_dequantize(cv, vs, cfg.dtype)
            else:
                ck, cv = cache_layer
            h, k0, v0 = self.block_decode(lp, h, ck, cv, cur_pos)
            return h, (k0, v0)

        # cache is READ-ONLY in the scan; per-layer new (k, v) come out as
        # tiny ys and are written in ONE batched scatter afterwards —
        # no per-layer slice copies / write-back round trips (§Perf A').
        xs = ((cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
              if cfg.kv_quant else (cache["k"], cache["v"]))
        x, (k_new, v_new) = self.mem.layer_scan(
            body, x, params["layers"], xs=xs,
            page_xs=cfg.pager.offload_kv, unroll=cfg.decode_unroll)
        slot = self._cache_slot(cache["k"].shape[3], cur_pos)
        bidx = jnp.arange(b)
        # advanced-index set: value layout (B, L, Hkv, hd)
        if cfg.kv_quant:
            kq, ks = L.kv_quantize(k_new)   # (L,B,H,hd) -> int8 + (L,B,H)
            vq, vs = L.kv_quantize(v_new)
            cache = {
                "k": cache["k"].at[:, bidx, :, slot].set(
                    kq.transpose(1, 0, 2, 3)),
                "v": cache["v"].at[:, bidx, :, slot].set(
                    vq.transpose(1, 0, 2, 3)),
                "k_scale": cache["k_scale"].at[:, bidx, :, slot].set(
                    ks.transpose(1, 0, 2)),
                "v_scale": cache["v_scale"].at[:, bidx, :, slot].set(
                    vs.transpose(1, 0, 2)),
            }
        else:
            cache = {
                "k": cache["k"].at[:, bidx, :, slot].set(
                    k_new.transpose(1, 0, 2, 3).astype(cache["k"].dtype)),
                "v": cache["v"].at[:, bidx, :, slot].set(
                    v_new.transpose(1, 0, 2, 3).astype(cache["v"].dtype)),
            }
        return x, cache

    def _decode_paged_cache(self, params: dict, x: jax.Array, cache: dict,
                            cur_pos: jax.Array):
        """FengHuang KV-offload decode: the cache rides in the scan CARRY
        (``paged_scan_cache``), each layer's slice paged in before
        attention and written back — with the current token's (k, v)
        merged in place — so only one layer's KV is device-resident."""
        cfg = self.cfg
        b = x.shape[0]
        slot = self._cache_slot(cache["k"].shape[3], cur_pos)
        bidx = jnp.arange(b)

        def body(h, lp, cache_layer):
            ck, cv = cache_layer
            h, k0, v0 = self.block_decode(lp, h, ck, cv, cur_pos)
            ck = ck.at[bidx, :, slot].set(k0.astype(ck.dtype))
            cv = cv.at[bidx, :, slot].set(v0.astype(cv.dtype))
            return h, (ck, cv)

        x, (ck, cv) = self.mem.layer_scan_cache(
            body, x, params["layers"], (cache["k"], cache["v"]))
        return x, {"k": ck, "v": cv}

    def _decode_pool(self, params: dict, x: jax.Array, cache: dict,
                     cur_pos: jax.Array, pages: jax.Array):
        """Block-pool paged decode: attention reads only the pages the
        (B, n_pages) table maps, so per-step cost scales with the actual
        sequence length instead of max_seq, and the new token's KV lands
        with ONE batched scatter over every layer and slot after the
        read-only layer scan.  With ``offload_kv`` the pools ride the
        scan carry instead (one layer's pool device-resident at a time,
        paged through the FengHuang remote tier)."""
        cfg = self.cfg
        b = x.shape[0]
        page = cache["k_pages"].shape[3]
        n_pages = pages.shape[1]
        bidx = jnp.arange(b)
        pi = cur_pos // page
        # writes past the mapped table (a finished slot re-feeding its
        # frozen position) are redirected to the null page 0 — never into
        # a live page of this or any other sequence
        pids = jnp.where(pi < n_pages,
                         pages[bidx, jnp.minimum(pi, n_pages - 1)], 0)
        slots = cur_pos % page
        quant = cfg.kv_quantized
        if quant:
            qdt, qmax = cfg.kv_pool_dtype(), cfg.kv_qmax()

        if cfg.pager.offload_kv:
            def body(h, lp, cl):
                if quant:
                    kp, vp, ksc, vsc = cl
                    h, k0, v0 = self.block_decode_paged(
                        lp, h, kp, vp, pages, cur_pos,
                        k_scales=ksc, v_scales=vsc)
                    k0, k0s = L.kv_pool_quantize(k0, qdt, qmax)
                    v0, v0s = L.kv_pool_quantize(v0, qdt, qmax)
                    ksc = ksc.at[pids, :, slots].set(k0s)
                    vsc = vsc.at[pids, :, slots].set(v0s)
                else:
                    kp, vp = cl
                    h, k0, v0 = self.block_decode_paged(lp, h, kp, vp, pages,
                                                        cur_pos)
                # (P, Hkv, page, hd).at[pids, :, slots] takes (B, Hkv, hd)
                kp = kp.at[pids, :, slots].set(k0.astype(kp.dtype))
                vp = vp.at[pids, :, slots].set(v0.astype(vp.dtype))
                return h, (kp, vp, ksc, vsc) if quant else (kp, vp)

            pools = ((cache["k_pages"], cache["v_pages"],
                      cache["k_scale"], cache["v_scale"]) if quant
                     else (cache["k_pages"], cache["v_pages"]))
            x, out = self.mem.layer_scan_cache(body, x, params["layers"],
                                               pools)
            cache = {"k_pages": out[0], "v_pages": out[1]}
            if quant:
                cache["k_scale"], cache["v_scale"] = out[2], out[3]
            return x, cache

        def body(h, lp, cl):
            scales = {"k_scales": cl[2], "v_scales": cl[3]} if quant else {}
            h, k0, v0 = self.block_decode_paged(lp, h, cl[0], cl[1], pages,
                                                cur_pos, **scales)
            return h, (k0, v0)

        xs = ((cache["k_pages"], cache["v_pages"],
               cache["k_scale"], cache["v_scale"]) if quant
              else (cache["k_pages"], cache["v_pages"]))
        x, (k_new, v_new) = self.mem.layer_scan(
            body, x, params["layers"], xs=xs,
            unroll=cfg.decode_unroll)
        # one scatter per pool for all L layers and B slots — the fix for
        # the old host-side PagePool.append's dispatch-per-token writes.
        # (L, P, Hkv, page, hd).at[:, pids, :, slots] takes its update as
        # (B, L, Hkv, hd): the separated advanced indices lead
        if quant:
            k_new, ks = L.kv_pool_quantize(k_new, qdt, qmax)
            v_new, vs = L.kv_pool_quantize(v_new, qdt, qmax)
        k_new = maybe_constraint(jnp.swapaxes(k_new, 0, 1),
                                 P(None, None, "model", None))
        v_new = maybe_constraint(jnp.swapaxes(v_new, 0, 1),
                                 P(None, None, "model", None))
        cache = dict(cache)
        cache["k_pages"] = cache["k_pages"].at[:, pids, :, slots].set(
            k_new.astype(cache["k_pages"].dtype))
        cache["v_pages"] = cache["v_pages"].at[:, pids, :, slots].set(
            v_new.astype(cache["v_pages"].dtype))
        if quant:
            ks = maybe_constraint(jnp.swapaxes(ks, 0, 1),
                                  P(None, None, "model"))
            vs = maybe_constraint(jnp.swapaxes(vs, 0, 1),
                                  P(None, None, "model"))
            cache["k_scale"] = cache["k_scale"].at[:, pids, :, slots].set(ks)
            cache["v_scale"] = cache["v_scale"].at[:, pids, :, slots].set(vs)
        return x, cache

    def decode_loop(self, params: dict, cache: dict, state: DecodeState, *,
                    num_steps: int, temperature: float = 0.0,
                    eos_id: int | None = None,
                    detect_nonfinite: bool = False):
        """Fused multi-step decode — see module-level :func:`decode_loop`."""
        return decode_loop(self, params, cache, state, num_steps=num_steps,
                           temperature=temperature, eos_id=eos_id,
                           detect_nonfinite=detect_nonfinite)


def vocab_mask_logits(logits: jax.Array, vocab: int) -> jax.Array:
    """Mask padded vocabulary columns to -inf."""
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(cols < vocab, logits, L.NEG_INF)


def sample_tokens(logits: jax.Array, vocab: int, temperature: float,
                  key: jax.Array) -> jax.Array:
    """logits: (B, 1, V) -> (B, 1) token ids (greedy for temperature<=0)."""
    logits = vocab_mask_logits(logits, vocab).astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def sample_tokens_per_slot(logits: jax.Array, vocab: int, temperature: float,
                           keys: jax.Array) -> jax.Array:
    """Per-slot sampling: logits (B, 1, V) with keys (B, 2) -> (B, 1).

    Each slot draws from its own PRNG key, so one slot's token never
    depends on which other slots happen to share the batch (the seam the
    preemption determinism contract rests on).  Greedy for
    temperature<=0, exactly like :func:`sample_tokens`."""
    logits = vocab_mask_logits(logits, vocab).astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(
        lambda lg, k: jax.random.categorical(k, lg / temperature, axis=-1)
    )(logits, keys).astype(jnp.int32)


def decode_loop(model, params: dict, cache: dict, state: DecodeState, *,
                num_steps: int, temperature: float = 0.0,
                eos_id: int | None = None, detect_nonfinite: bool = False,
                emit_logits: bool = False):
    """Fused on-device decode: ``num_steps`` tokens in ONE dispatch.

    A ``lax.scan`` over decode steps — any model exposing
    ``decode_step(params, tokens, cache, cur_pos)`` works.  Per-slot
    ``active``/``remaining`` masks turn finished sequences into no-ops:
    their fed token and write position freeze, so a drained slot neither
    advances nor perturbs live neighbours, and the emitted ``valid`` mask
    tells the host which tokens are real.

    PRNG semantics depend on ``state.slot_keys``:

    * ``None`` (legacy): the batch-wide key is split exactly like the
      host-driven per-token loop (``key, k = split(key)`` per step), so
      block decoding is bit-identical to per-token decoding at any
      temperature — but a token then depends on the global step count.
    * per-slot keys (serving): the token a slot emits at sequence
      position ``q`` is sampled from ``fold_in(slot_key, q)`` via
      :func:`sample_tokens_per_slot` — a pure function of the request's
      own key and position, invariant under preemption/resume, block
      boundaries and batch composition.

    Returns ``(tokens (B, num_steps), valid (B, num_steps), cache,
    state)``.  Callers should jit this with the cache and state donated
    (:func:`repro.memory.donating_jit`) so the KV cache is aliased in
    place across dispatches — the decode-side donation contract of
    :class:`repro.models.base.DecodeState`.

    ``detect_nonfinite=True`` additionally emits a per-slot, per-step
    **poison mask** (True where an *emitting* slot sampled from
    non-finite logits — NaN/inf from corrupted KV or an overflowed
    activation) between ``valid`` and ``cache`` in the return tuple:
    ``(tokens, valid, poison, cache, state)``.  The serving harvest
    uses it to shed ONLY the poisoned sequence instead of letting one
    request's NaN silently corrupt a whole batch's sampled stream.
    Slots that are inactive at a step are never flagged (their frozen
    garbage is harmless by construction).

    ``emit_logits=True`` appends each step's logits, ``(B, num_steps,
    V)`` in the model dtype, right before ``cache`` — what a caller
    compares against a reference forward.
    """
    vocab = model.cfg.vocab
    cache = model.mem.kv_in(cache)   # the scan carries a device cache

    def step(carry, _):
        cache, st = carry
        key, k = jax.random.split(st.key)
        if st.pages is None:
            logits, cache = model.decode_step(params, st.tokens, cache,
                                              st.pos)
        else:   # block-pool paged cache: st.pos doubles as seq_lens
            logits, cache = model.decode_step(params, st.tokens, cache,
                                              st.pos, pages=st.pages)
        if st.slot_keys is None:
            nxt = sample_tokens(logits, vocab, temperature, k)
        else:
            # the sampled token lands at sequence position pos + 1
            step_keys = jax.vmap(jax.random.fold_in)(
                st.slot_keys, (st.pos + 1).astype(jnp.uint32))
            nxt = sample_tokens_per_slot(logits, vocab, temperature,
                                         step_keys)
        # freeze finished slots: keep re-feeding the last token in place
        nxt = jnp.where(st.active[:, None], nxt, st.tokens)
        emitted = st.active
        pos = st.pos + emitted.astype(st.pos.dtype)
        remaining = st.remaining - emitted.astype(st.remaining.dtype)
        active = st.active & (remaining > 0)
        if eos_id is not None:
            active = active & (nxt[:, 0] != eos_id)
        new_state = DecodeState(tokens=nxt, pos=pos, active=active,
                                remaining=remaining, key=key, pages=st.pages,
                                slot_keys=st.slot_keys)
        out = (nxt[:, 0], emitted)
        if detect_nonfinite:
            bad = (~jnp.isfinite(logits).all(axis=(1, 2))) & emitted
            out = out + (bad,)
        if emit_logits:
            out = out + (logits[:, 0],)
        return (cache, new_state), out

    (cache, state), outs = jax.lax.scan(
        step, (cache, state), None, length=num_steps)
    outs = tuple(jnp.swapaxes(o, 0, 1) for o in outs)
    return outs + (cache, state)
