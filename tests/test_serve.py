"""Serving runtime: batched server end-to-end + sampling semantics +
the fused block-decode loop and continuous batching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, build_model
from repro.models.base import DecodeState
from repro.models.transformer import decode_loop
from repro.runtime.serve import (BatchedServer, make_decode_loop,
                                 make_serve_step, sample)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_sample_greedy_masks_padded_vocab():
    logits = jnp.zeros((2, 1, 512))
    # put the max in the PADDED region — must never be sampled
    logits = logits.at[:, :, 500:].set(100.0)
    toks = sample(logits, vocab=500, temperature=0.0,
                  key=jax.random.PRNGKey(0))
    assert int(toks.max()) < 500


def test_server_serves_batch(tiny_model):
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=2, max_seq=64)
    r1 = server.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=6)
    r2 = server.submit(np.asarray([9, 10], np.int32), max_new_tokens=6)
    done = server.run_once()
    assert {r.uid for r in done} == {r1.uid, r2.uid}
    assert len(r1.output) == 6 and len(r2.output) == 6
    assert all(0 <= t < model.cfg.vocab for t in r1.output)
    assert server.stats["tokens"] > 0


def test_server_greedy_deterministic(tiny_model):
    model, params = tiny_model
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    outs = []
    for _ in range(2):
        server = BatchedServer(model, params, batch_size=1, max_seq=64)
        r = server.submit(prompt, max_new_tokens=8)
        server.run_once()
        outs.append(tuple(r.output))
    assert outs[0] == outs[1]


def _prefilled(model, params, batch, plen, max_seq=64):
    prompts = jax.random.randint(jax.random.PRNGKey(3), (batch, plen), 0,
                                 model.cfg.vocab)
    cache = model.init_cache(batch, max_seq)
    logits, cache = jax.jit(lambda p, t, c: model.prefill(p, t, c))(
        params, prompts, cache)
    cur = sample(logits, model.cfg.vocab, 0.0, jax.random.PRNGKey(0))
    return cur, cache


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_decode_loop_matches_per_token(tiny_model, temperature):
    """Block decode == the old host-driven per-token loop, bit-exact:
    greedy AND sampled (same per-step PRNG folding)."""
    model, params = tiny_model
    batch, plen, steps = 2, 8, 6
    cur, cache = _prefilled(model, params, batch, plen)
    key0 = jax.random.PRNGKey(7)

    sstep = jax.jit(make_serve_step(model, temperature=temperature))
    key, ref_cache, c = key0, cache, cur
    pos = jnp.full((batch,), plen, jnp.int32)
    ref = []
    for _ in range(steps):
        key, k = jax.random.split(key)
        c, _, ref_cache = sstep(params, c, ref_cache, pos, k)
        pos = pos + 1
        ref.append(np.asarray(c[:, 0]))
    ref = np.stack(ref, axis=1)

    state = DecodeState(tokens=cur, pos=jnp.full((batch,), plen, jnp.int32),
                        active=jnp.ones((batch,), bool),
                        remaining=jnp.full((batch,), steps, jnp.int32),
                        key=key0)
    toks, valid, blk_cache, _ = jax.jit(
        lambda p, ca, st: decode_loop(model, p, ca, st, num_steps=steps,
                                      temperature=temperature))(
        params, cache, state)
    np.testing.assert_array_equal(ref, np.asarray(toks))
    assert np.asarray(valid).all()
    np.testing.assert_array_equal(np.asarray(ref_cache["k"], np.float32),
                                  np.asarray(blk_cache["k"], np.float32))


def test_decode_loop_masks_freeze_finished_slots(tiny_model):
    """A drained slot stops emitting (valid=False), freezes its pos, and
    does not perturb the tokens of still-active neighbours."""
    model, params = tiny_model
    batch, plen, steps = 2, 8, 6
    cur, cache = _prefilled(model, params, batch, plen)

    def run(remaining):
        state = DecodeState(
            tokens=cur, pos=jnp.full((batch,), plen, jnp.int32),
            active=jnp.asarray(remaining) > 0,
            remaining=jnp.asarray(remaining, jnp.int32),
            key=jax.random.PRNGKey(7))
        return jax.jit(lambda p, ca, st: decode_loop(
            model, p, ca, st, num_steps=steps))(params, cache, state)

    toks_all, valid_all, _, _ = run([steps, steps])
    toks, valid, _, state = run([steps, 2])
    valid = np.asarray(valid)
    assert valid[0].all() and valid[1, :2].all() and not valid[1, 2:].any()
    assert int(state.pos[1]) == plen + 2 and not bool(state.active[1])
    # slot 1 freezes its fed token after draining
    assert (np.asarray(toks)[1, 2:] == np.asarray(toks)[1, 1]).all()
    # slot 0 is untouched by slot 1 finishing
    np.testing.assert_array_equal(np.asarray(toks)[0], np.asarray(toks_all)[0])
    # and the frozen slot's valid prefix matches the all-active run
    np.testing.assert_array_equal(np.asarray(toks)[1, :2],
                                  np.asarray(toks_all)[1, :2])


def test_decode_loop_donates_cache_and_state(tiny_model):
    """The jitted loop consumes (cache, state): donated buffers die."""
    model, params = tiny_model
    cur, cache = _prefilled(model, params, 2, 8)
    state = DecodeState(tokens=cur, pos=jnp.full((2,), 8, jnp.int32),
                        active=jnp.ones((2,), bool),
                        remaining=jnp.full((2,), 4, jnp.int32),
                        key=jax.random.PRNGKey(0))
    loop = make_decode_loop(model, block_size=4)
    _, _, new_cache, _ = loop(params, cache, state)
    if not cache["k"].is_deleted():
        pytest.skip("backend does not implement buffer donation")
    assert cache["k"].is_deleted() and cache["v"].is_deleted()
    assert state.tokens.is_deleted()
    assert not new_cache["k"].is_deleted()


def test_server_one_dispatch_and_sync_per_block(tiny_model):
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=2, max_seq=64,
                           block_size=4)
    server.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=9)
    server.submit(np.asarray([9, 10], np.int32), max_new_tokens=9)
    server.run_once()
    # 8 decode tokens per slot after prefill -> 2 blocks of 4
    assert server.stats["blocks"] == 2
    assert server.stats["dispatches"] == server.stats["blocks"]
    assert server.stats["host_syncs"] == server.stats["blocks"]
    assert server.stats["tokens"] == 18


def test_continuous_batching_admits_mid_stream(tiny_model):
    """3 requests, 2 slots, ONE batch: the third request joins the live
    batch when a slot frees — no restart, no re-prefill of neighbours."""
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=2, max_seq=64,
                           block_size=4)
    ra = server.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=12)
    rb = server.submit(np.asarray([4, 5], np.int32), max_new_tokens=3)
    rc = server.submit(np.asarray([6], np.int32), max_new_tokens=5)
    done = server.run_once()
    assert {r.uid for r in done} == {ra.uid, rb.uid, rc.uid}
    assert server.stats["batches"] == 1          # the batch never restarted
    assert [len(r.output) for r in (ra, rb, rc)] == [12, 3, 5]
    assert server.stats["admitted"] == 3
    # long request must be identical to a solo run (mid-stream admission
    # of rc into rb's slot didn't disturb it)
    solo = BatchedServer(model, params, batch_size=2, max_seq=64,
                         block_size=4)
    rs = solo.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=12)
    solo.run_once()
    assert rs.output == ra.output


def test_admission_edge_cases(tiny_model):
    """Oversized work is rejected at submit (the caller's frame, so no
    dequeued request is ever dropped); tight-fitting requests never write
    KV past the cache end; EOS sampled at admission finishes the request
    without ever activating the slot on device."""
    model, params = tiny_model
    server = BatchedServer(model, params, batch_size=1, max_seq=32)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        server.submit(np.arange(40, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        server.submit(np.arange(9, dtype=np.int32), max_new_tokens=25)

    # 9 + 15 tokens in max_seq=24: bucket(9)=16 would overflow, so
    # admission falls back to the exact length and every write fits
    tight = BatchedServer(model, params, batch_size=1, max_seq=24,
                          block_size=4)
    r = tight.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=15)
    tight.run_once()
    assert len(r.output) == 15
    assert int(np.asarray(tight.state.pos)[0]) <= 24

    probe = BatchedServer(model, params, batch_size=1, max_seq=64)
    r = probe.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=12)
    probe.run_once()
    eos = r.output[0]
    server2 = BatchedServer(model, params, batch_size=1, max_seq=64,
                            eos_id=eos)
    r2 = server2.submit(np.asarray([3, 1, 4], np.int32), max_new_tokens=12)
    done = server2.run_once()
    assert done == [r2] and r2.output == [eos]
    assert server2.stats["blocks"] == 0           # no ghost decode dispatch
    assert not bool(np.asarray(server2.state.active).any())


def test_server_uses_configured_temperature(tiny_model):
    """Seed-sensitive outputs prove the post-prefill sample no longer
    hardcodes temperature=0.0 (the seed-repo bug)."""
    model, params = tiny_model
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)

    def first_token(seed):
        server = BatchedServer(model, params, batch_size=1, max_seq=64,
                               temperature=2.0, seed=seed)
        r = server.submit(prompt, max_new_tokens=8)
        server.run_once()
        return r.output[0]

    # with the old hardcoded temperature=0.0 the first token is greedy,
    # hence identical for every seed; at temperature 2.0 it must vary
    assert len({first_token(s) for s in range(4)}) > 1


@pytest.mark.parametrize("paged", [False, True])
def test_server_records_logits_of_every_emitted_token(tiny_model, paged):
    """record_logits keeps the logits each token was sampled from: at
    temperature 0 every token is the argmax of its own logits, across
    admission (prefill) and block decode, with no effect on tokens."""
    model, params = tiny_model
    prompts = [np.asarray([5, 6, 7], np.int32), np.asarray([9, 10], np.int32),
               np.asarray([1, 2, 3, 4], np.int32)]

    def serve(record):
        server = BatchedServer(model, params, batch_size=2, max_seq=64,
                               paged=paged, record_logits=record)
        reqs = [server.submit(p, max_new_tokens=7) for p in prompts]
        server.run_once()
        return reqs

    plain, recorded = serve(False), serve(True)
    for a, r in zip(plain, recorded):
        assert r.output == a.output and a.logits == []
        assert len(r.logits) == len(r.output) == 7
        vocab = model.cfg.vocab
        assert [int(np.argmax(lg[:vocab])) for lg in r.logits] == r.output
