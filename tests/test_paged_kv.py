"""Block-pool paged KV cache: kernel edge cases, BlockManager invariants,
and end-to-end paged-vs-dense serving parity."""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.kernels.paged_attention import ops as pa
from repro.kernels.paged_attention.ops import BlockManager
from repro.kernels.paged_attention.ref import gather_pages
from repro.memory import BlockPoolResidency
from repro.models.base import DecodeState
from repro.models.layers import decode_attention, paged_decode_attention
from repro.models.transformer import decode_loop
from repro.runtime.serve import BatchedServer

RNG = np.random.RandomState(7)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ---------------------------------------------------------------------------
# kernel edge cases: kernel (interpret) vs gather oracle vs dense attention
# ---------------------------------------------------------------------------

def _pool(b, npages, page, hkv, d, dtype=jnp.float32):
    pool = 1 + b * npages
    kp = jnp.asarray(RNG.randn(pool, hkv, page, d), dtype) * 0.3
    vp = jnp.asarray(RNG.randn(pool, hkv, page, d), dtype)
    table = jnp.asarray(1 + np.arange(b * npages).reshape(b, npages),
                        jnp.int32)
    return kp, vp, table


@pytest.mark.parametrize("lens", [
    (5, 11),      # partial last page on both rows
    (0, 12),      # empty slot next to a live one
    (8, 16),      # exact page boundaries
])
@pytest.mark.parametrize("g", [1, 3])       # GQA group of 1 and > 1
def test_paged_kernel_edge_cases(lens, g):
    b, hkv, d, page, npages = 2, 2, 16, 8, 2
    kp, vp, table = _pool(b, npages, page, hkv, d)
    q = jnp.asarray(RNG.randn(b, hkv, g, d), jnp.float32) * 0.3
    seq_lens = jnp.asarray(lens, jnp.int32)
    k0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32) * 0.3
    v0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32)

    out = pa.attend(q, kp, vp, table, seq_lens, (k0, v0), interpret=True)
    ref = pa.attend_ref(q, kp, vp, table, seq_lens, (k0, v0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # ... and against the DENSE decode path over the gathered view — the
    # parity that makes paged serving bit-compatible with the dense cache
    hq = hkv * g
    qd = q.reshape(b, 1, hq, d)
    kd, vd = gather_pages(kp, table), gather_pages(vp, table)
    dense = decode_attention(qd, kd, vd, seq_lens, extra_kv=(k0, v0))
    np.testing.assert_allclose(
        np.asarray(out).reshape(b, 1, hq, d), np.asarray(dense),
        atol=2e-5, rtol=2e-5)


def test_paged_kernel_null_page_padding():
    """Table columns past a sequence's pages map the null page 0; its
    contents must never leak into the output."""
    b, hkv, g, d, page = 1, 2, 2, 16, 8
    kp, vp, table = _pool(b, 3, page, hkv, d)
    # poison the null page, then point the last table column at it
    kp = kp.at[0].set(100.0)
    vp = vp.at[0].set(-100.0)
    table = table.at[0, 2].set(0)
    q = jnp.asarray(RNG.randn(b, hkv, g, d), jnp.float32) * 0.3
    k0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32) * 0.3
    v0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32)
    seq_lens = jnp.asarray([13], jnp.int32)       # inside the real pages

    out = pa.attend(q, kp, vp, table, seq_lens, (k0, v0), interpret=True)
    short = pa.attend(q, kp, vp, table[:, :2], seq_lens, (k0, v0),
                      interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(short),
                               atol=2e-5, rtol=2e-5)
    assert np.abs(np.asarray(out)).max() < 50


def test_paged_kernel_seq_len_zero_with_self_column():
    """A fresh slot (seq_len 0) must attend ONLY the current token."""
    b, hkv, g, d, page = 1, 2, 2, 16, 8
    kp, vp, table = _pool(b, 2, page, hkv, d)
    q = jnp.asarray(RNG.randn(b, hkv, g, d), jnp.float32) * 0.3
    k0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32) * 0.3
    v0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32)
    out = pa.attend(q, kp, vp, table, jnp.asarray([0], jnp.int32),
                    (k0, v0), interpret=True)
    # softmax over a single column == that column's value
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(np.asarray(v0)[:, :, None, :],
                                         (b, hkv, g, d)),
        atol=2e-5, rtol=2e-5)


def _live_case(b, hkv, g, width, page, d=32):
    kp, vp, table = _pool(b, width, page, hkv, d)
    q = jnp.asarray(RNG.randn(b, hkv, g, d), jnp.float32) * 0.3
    k0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32) * 0.3
    v0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32)
    return q, kp, vp, table, (k0, v0)


@pytest.mark.parametrize("width,lens", [
    (1, (16, 5)),        # tables narrower than a block of 128 positions
    (2, (17, 32)),
    (4, (0, 64)),        # seq_len 0 beside a full table
    (16, (128, 129)),    # on a block boundary (8 pages of 16), one past
    (16, (256, 1)),      # the whole table, and a single position
])
@pytest.mark.parametrize("hkv", [1, 2])      # 2: a tp=4 head shard
def test_paged_kernel_live_blocks(width, lens, hkv):
    """The kernel fetches whole blocks of live pages (16-position pages,
    blocks of up to 8) and masks past ``seq_lens``: it matches the gather
    oracle at block edges and for tables narrower than a block."""
    q, kp, vp, table, kv0 = _live_case(2, hkv, 3, width, 16)
    seq_lens = jnp.asarray(lens, jnp.int32)
    out = pa.attend(q, kp, vp, table, seq_lens, kv0, interpret=True)
    ref = pa.attend_ref(q, kp, vp, table, seq_lens, kv0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("frozen", [5, 100])
def test_paged_kernel_dead_slot_reads_no_page(frozen):
    """An evicted slot keeps its frozen length while its row is nulled:
    the kernel reads none of its pages — the null page, poisoned with
    NaN here, never reaches the output — and it attends its current
    token alone; the live slot beside it is unaffected."""
    b, hkv, g, d, width, page = 2, 2, 3, 32, 8, 16
    q, kp, vp, table, (k0, v0) = _live_case(b, hkv, g, width, page, d)
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    table = table.at[1].set(0)
    seq_lens = jnp.asarray([40, frozen], jnp.int32)
    out = np.asarray(pa.attend(q, kp, vp, table, seq_lens, (k0, v0),
                               interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out[1], np.broadcast_to(np.asarray(v0)[1, :, None, :],
                                (hkv, g, d)), atol=2e-5, rtol=2e-5)
    ref = pa.attend_ref(q, kp, vp, table, seq_lens, (k0, v0))
    np.testing.assert_allclose(out[0], np.asarray(ref)[0],
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_attention_backend_routing(monkeypatch):
    """Kernel (interpret) and gather fallback agree through the layer-level
    entry point, q in (B, 1, Hq, hd) layout."""
    import functools
    monkeypatch.setattr(pa, "paged_attention", functools.partial(
        pa.paged_attention, interpret=True))
    b, hkv, g, d, page = 2, 2, 2, 16, 8
    kp, vp, table = _pool(b, 2, page, hkv, d)
    q = jnp.asarray(RNG.randn(b, 1, hkv * g, d), jnp.float32) * 0.3
    k0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32) * 0.3
    v0 = jnp.asarray(RNG.randn(b, hkv, d), jnp.float32)
    cur = jnp.asarray([7, 15], jnp.int32)
    a = paged_decode_attention(q, kp, vp, table, cur, (k0, v0),
                               use_kernel=False)
    k = paged_decode_attention(q, kp, vp, table, cur, (k0, v0),
                               use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# BlockManager invariants
# ---------------------------------------------------------------------------

def test_block_manager_alloc_free_reuse_churn():
    mgr = BlockManager(num_pages=17, page_size=4)
    rng = random.Random(0)
    live: dict[int, int] = {}       # slot -> tokens
    for step in range(200):
        if live and (rng.random() < 0.4 or len(live) >= 4):
            slot = rng.choice(list(live))
            mgr.free_slot(slot)
            del live[slot]
        else:
            slot = rng.randrange(8)
            if slot in live:
                tokens = live[slot] + rng.randrange(1, 9)
            else:
                tokens = rng.randrange(1, 17)
            if mgr.pages_for(tokens) - len(mgr.slot_pages(slot)) \
                    > mgr.free_pages:
                continue
            mgr.ensure(slot, tokens)
            mgr.note_tokens(slot, tokens)
            live[slot] = tokens
        # invariants: no double ownership, null page never allocated,
        # conservation, coverage
        owned = [p for t in mgr.pages.values() for p in t]
        assert len(owned) == len(set(owned))
        assert 0 not in owned and 0 not in mgr._free
        assert len(owned) + mgr.free_pages == mgr.capacity
        for slot, tokens in live.items():
            assert len(mgr.slot_pages(slot)) >= mgr.pages_for(tokens)
        assert 0.0 <= mgr.fragmentation() < 1.0
    for slot in list(live):
        mgr.free_slot(slot)
    assert mgr.free_pages == mgr.capacity and mgr.pages_in_use == 0
    assert mgr.hwm > 0


def test_block_manager_exhaustion_and_null_page():
    mgr = BlockManager(num_pages=3, page_size=4)
    mgr.ensure(0, 8)                        # both allocatable pages
    with pytest.raises(MemoryError, match="exhausted"):
        mgr.ensure(1, 1)
    assert mgr.can_fit(0, 8) and not mgr.can_fit(1, 1)
    tab = mgr.table([0, 1], 3)
    assert tab.shape == (2, 3)
    assert tab[1].tolist() == [0, 0, 0]     # unallocated -> null page
    assert tab[0, 2] == 0                   # width padding -> null page


def test_block_pool_residency_batched_append():
    """Host-side BlockPoolResidency pools (the deleted PagePool's role):
    chunked append_block == one append_block, page-boundary crossing."""
    kw = dict(num_pages=8, page_size=4, kv_heads=2, head_dim=8)
    a = BlockPoolResidency(**kw)
    b = BlockPoolResidency(**kw)
    a.alloc_seq(1)
    b.alloc_seq(1)
    blk_k = jnp.asarray(RNG.randn(6, 2, 8), jnp.bfloat16)
    blk_v = jnp.asarray(RNG.randn(6, 2, 8), jnp.bfloat16)
    for lo, hi in ((0, 2), (2, 3), (3, 6)):      # three uneven chunks
        a.append_block(1, blk_k[lo:hi], blk_v[lo:hi])
    b.append_block(1, blk_k, blk_v)
    assert a.manager.lens[1] == b.manager.lens[1] == 6
    assert a.manager.pages[1] == b.manager.pages[1]
    np.testing.assert_array_equal(np.asarray(a.k, np.float32),
                                  np.asarray(b.k, np.float32))
    assert a.batch_lens([1]).tolist() == [6]
    assert a.batch_tables([1], 3).shape == (1, 3)
    a.free_seq(1)
    assert 1 not in a.manager.pages


# ---------------------------------------------------------------------------
# model-level parity: paged prefill/decode vs the dense cache path
# ---------------------------------------------------------------------------

def _dense_and_paged(model, params, batch, plen, max_seq, steps, page=16):
    cfg = model.cfg
    prompts = jax.random.randint(jax.random.PRNGKey(3), (batch, plen), 0,
                                 cfg.vocab)
    cache_d = model.init_cache(batch, max_seq)
    lg_d, cache_d = jax.jit(lambda p, t, c: model.prefill(p, t, c))(
        params, prompts, cache_d)

    mgr = BlockManager(1 + batch * (-(-max_seq // page)), page)
    cache_p = model.init_paged_cache(mgr.num_pages, page)
    for i in range(batch):
        mgr.ensure(i, plen + steps)
    n_prompt = mgr.pages_for(plen)
    prompt_pages = jnp.asarray(
        [mgr.slot_pages(i)[:n_prompt] for i in range(batch)], jnp.int32)
    lg_p, cache_p = jax.jit(lambda p, t, c, pg: model.prefill_paged(
        p, t, c, pg))(params, prompts, cache_p, prompt_pages)
    table = jnp.asarray(mgr.table(list(range(batch)),
                                  mgr.max_slot_pages()), jnp.int32)
    return (lg_d, cache_d), (lg_p, cache_p, table)


def test_paged_prefill_matches_dense(tiny_model):
    model, params = tiny_model
    (lg_d, cache_d), (lg_p, cache_p, table) = _dense_and_paged(
        model, params, batch=2, plen=8, max_seq=64, steps=6)
    np.testing.assert_array_equal(np.asarray(lg_d), np.asarray(lg_p))
    # every layer's pages hold exactly the dense cache's prompt KV
    for l in range(model.cfg.num_layers):
        kg = gather_pages(cache_p["k_pages"][l], table)
        np.testing.assert_array_equal(
            np.asarray(kg[:, :, :8], np.float32),
            np.asarray(cache_d["k"][l][:, :, :8], np.float32))


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_paged_decode_loop_matches_dense(tiny_model, temperature):
    """Greedy AND sampled parity: the paged pool emits bit-identical
    tokens to the dense cache under the same PRNG folding."""
    model, params = tiny_model
    batch, plen, steps = 2, 8, 6
    (lg_d, cache_d), (lg_p, cache_p, table) = _dense_and_paged(
        model, params, batch, plen, max_seq=64, steps=steps)
    cur = jnp.argmax(np.asarray(lg_d), axis=-1).astype(jnp.int32)
    common = dict(tokens=cur, pos=jnp.full((batch,), plen, jnp.int32),
                  active=jnp.ones((batch,), bool),
                  remaining=jnp.full((batch,), steps, jnp.int32),
                  key=jax.random.PRNGKey(7))
    run = jax.jit(lambda p, c, s: decode_loop(
        model, p, c, s, num_steps=steps, temperature=temperature))
    t_d, v_d, _, _ = run(params, cache_d, DecodeState(**common))
    t_p, v_p, _, _ = run(params, cache_p, DecodeState(**common, pages=table))
    np.testing.assert_array_equal(np.asarray(t_d), np.asarray(t_p))
    np.testing.assert_array_equal(np.asarray(v_d), np.asarray(v_p))


# ---------------------------------------------------------------------------
# server end-to-end
# ---------------------------------------------------------------------------

def test_server_paged_matches_dense_server(tiny_model):
    model, params = tiny_model
    prompts = [np.asarray([3, 1, 4, 1, 5], np.int32),
               np.asarray([9, 10], np.int32),
               np.asarray([6], np.int32)]

    def serve(paged):
        server = BatchedServer(model, params, batch_size=2, max_seq=64,
                               block_size=4, paged=paged)
        reqs = [server.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (9, 5, 7))]
        server.run_once()
        return server, [tuple(r.output) for r in reqs]

    sp, out_p = serve(True)
    sd, out_d = serve(False)
    assert sp.paged and not sd.paged
    assert out_p == out_d
    # continuous batching stayed intact and every page was reclaimed
    assert sp.stats["admitted"] == 3 and sp.stats["batches"] == 1
    assert sp.manager.pages_in_use == 0
    assert sp.manager.free_pages == sp.manager.capacity
    assert sp.stats["kv_pages_hwm"] > 0
    assert sp.kv_bytes_in_use() == 0


def test_server_paged_footprint_tracks_live_tokens(tiny_model):
    """KV pages consumed scale with actual tokens, not batch x max_seq."""
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=4, max_seq=256,
                           block_size=4, page_size=16)
    server.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=8)
    server.submit(np.asarray([4, 5], np.int32), max_new_tokens=8)
    server.run_once()
    # 2 slots x (8-token prompt bucket + 8 decode) = 1 page each; the
    # dense cache would hold 4 x 256 tokens = 64 pages worth
    assert server.manager.hwm <= 2
    dense_tokens = 4 * 256
    live_tokens = 2 * 16
    assert server.manager.hwm * 16 <= 2 * live_tokens
    assert server.kv_bytes_capacity() \
        == server.num_pages * 16 * model.cfg.padded_kv_heads \
        * model.cfg.head_dim * 2 * model.cfg.num_layers * 2
    assert dense_tokens // 16 == 64       # the slab the pool replaced


def test_server_paged_admission_backpressure(tiny_model):
    """A pool smaller than the worst case of two concurrent requests
    serializes them via admission instead of dying mid-decode."""
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=2, max_seq=32,
                           num_pages=4, page_size=8)
    a = server.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=16)
    b = server.submit(np.arange(9, 17, dtype=np.int32), max_new_tokens=16)
    done = server.run_once()
    assert {r.uid for r in done} == {a.uid, b.uid}
    assert len(a.output) == len(b.output) == 16
    assert server.manager.hwm <= server.manager.capacity
    assert server.manager.free_pages == server.manager.capacity
    # oversized-for-the-pool requests are rejected up front
    with pytest.raises(ValueError, match="KV pages"):
        server.submit(np.arange(1, 17, dtype=np.int32), max_new_tokens=17)


def test_evicted_slot_ghost_writes_never_corrupt_reused_pages(tiny_model):
    """A slot that finishes early keeps being executed (inactive, frozen
    position) by every later dispatch; its page-table row must be
    re-pointed at the null page so those ghost writes can never land in
    its freed pages once a live neighbour's growth reuses them (LIFO
    free order makes reuse immediate).  Tiny pages + blocks maximise
    page churn after the eviction."""
    model, params = tiny_model
    long_prompt = np.asarray([3, 1, 4, 1, 5], np.int32)

    def long_output(with_neighbour):
        server = BatchedServer(model, params, batch_size=2, max_seq=64,
                               block_size=2, page_size=2)
        req = server.submit(long_prompt, max_new_tokens=24)
        if with_neighbour:     # finishes after one block, pages reused
            server.submit(np.asarray([9, 10], np.int32), max_new_tokens=2)
        server.run_once()
        return tuple(req.output)

    assert long_output(True) == long_output(False)


def test_server_paged_offload_kv(tiny_model):
    """offload_kv composes: the pool rides the scan carry through the
    remote tier and still emits identical tokens."""
    model, params = tiny_model
    ocfg = model.cfg.with_pager(enabled=True, offload_kv=True)
    omodel = build_model(ocfg)
    prompt = np.asarray([3, 1, 4], np.int32)

    def run(m):
        server = BatchedServer(m, params, batch_size=2, max_seq=64)
        r = server.submit(prompt, max_new_tokens=8)
        server.run_once()
        return r.output

    assert run(omodel) == run(model)

def test_server_offloaded_pool_rests_in_remote_tier(tiny_model):
    """Between dispatches an offload_kv server keeps its page pools in
    the remote tier (pinned host memory), not where the jitted step
    returned them."""
    model, params = tiny_model
    omodel = build_model(model.cfg.with_pager(enabled=True,
                                              offload_kv=True))
    server = BatchedServer(omodel, params, batch_size=2, max_seq=64)
    server.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=4)
    server.run_once()
    for k in ("k_pages", "v_pages"):
        assert server.cache[k].sharding.memory_kind == "pinned_host"
