"""BatchedServer's own measurements: the request stamps (``t_submit``,
``t_admit``), the paged kernel's grid counters (``kv_pages_visited``,
``kv_pages_live``), the backlog's blocked time by cause
(``queue_blocked_pages_s`` / ``queue_blocked_slots_s``) and the host
spans (``serve.*``) it writes into a profiler trace."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import build_model, get_config
from repro.runtime.serve import BatchedServer

PAGE = 4
MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, remat=False, page_size=PAGE)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _server(tiny_model, **kw):
    model, params = tiny_model
    kw.setdefault("batch_size", 3)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_size", 4)
    return BatchedServer(model, params, **kw)


def _drive(server, reqs, max_rounds=60):
    for _ in range(max_rounds):
        server.run_once()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError(
        f"requests stuck: {[(r.uid, r.done.is_set()) for r in reqs]}")


def _submit(server, shapes):
    rng = np.random.default_rng(0)
    return [server.submit(rng.integers(1, 500, size=p).astype(np.int32),
                          max_new_tokens=m) for p, m in shapes]


# ---------------------------------------------------------------------------
# request stamps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefill_async", [False, True])
def test_every_admitted_request_is_stamped_in_order(tiny_model,
                                                    prefill_async):
    kw = dict(prefill_async=True, prefill_chunk_tokens=8) \
        if prefill_async else {}
    srv = _server(tiny_model, batch_size=2, **kw)
    reqs = _submit(srv, [(6, 8), (24, 6), (3, 10), (13, 6), (9, 1)])
    _drive(srv, reqs)
    for r in reqs:
        assert r.error is None
        assert r.t_submit is not None and r.t_admit is not None
        assert r.t_submit <= r.t_admit


def test_a_resumed_request_keeps_its_first_admission_stamp(tiny_model):
    """Two 8-page worst cases fill a 17-page pool, so the third request
    preempts a victim, which later resumes."""
    srv = _server(tiny_model, num_pages=18, block_size=8)
    seen = {}
    resume = srv._resume

    def recording_resume(ps, slot, finished):
        seen[ps.req.uid] = ps.req.t_admit
        return resume(ps, slot, finished)

    srv._resume = recording_resume
    reqs = [srv.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=24)
            for _ in range(3)]
    _drive(srv, reqs)
    assert srv.stats["resumes"] >= 1 and seen
    by_uid = {r.uid: r for r in reqs}
    for uid, stamp in seen.items():
        assert stamp is not None
        assert by_uid[uid].t_admit == stamp


# ---------------------------------------------------------------------------
# the paged kernel's grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", [True, False])
def test_kv_page_counters_match_a_hand_count(tiny_model, pipeline):
    """Two slots, blocks of 4 steps, pages of 4 positions; both prompts
    pad to 8 positions.  A (9 tokens: 1 from the prefill, 8 decoded)
    runs two blocks; B (3 tokens) advances 2 steps of the first block
    and finishes there.  The table is 4 pages wide in both blocks.

    visited: the kernel's blocks span 4 pages, the table's width, so a
    slot whose row is mapped fetches 4 columns at each step.  Block 1:
    A and B, 2 x 4 steps x 4 = 32 (B keeps its row and frozen length
    after it stops).  Block 2: A 16, and B 16 more when pipelined, since
    block 2 then goes out before block 1's harvest frees B's row: 64
    pipelined, 48 not.
    live, pages holding the pooled positions each step attends:
    block 1, A at 8..11 -> 2+3+3+3 = 11, B at 8..9 -> 2+3 = 5;
    block 2, A at 12..15 -> 3+4+4+4 = 15; in all 31."""
    srv = _server(tiny_model, batch_size=2, pipeline=pipeline)
    a, b = _submit(srv, [(8, 9), (8, 3)])
    _drive(srv, [a, b])
    assert (len(a.output), len(b.output)) == (9, 3)
    assert srv.stats["blocks"] == 2
    assert srv.stats["kv_pages_visited"] == (64 if pipeline else 48)
    assert srv.stats["kv_pages_live"] == 31


@pytest.mark.parametrize("pipeline", [True, False])
def test_kv_pages_visited_follows_the_kernels_block_rule(
        tiny_model, monkeypatch, pipeline):
    """Every call of the paged kernel in a served run records the table
    and lengths it was given; the server's count equals what the
    kernel's own rule fetches for them, per layer.  Slots finish at
    different steps and one slot is freed while others run, so the
    tables carry nulled rows with frozen lengths, which fetch nothing."""
    from repro.kernels.paged_attention import kernel as pk
    from repro.kernels.paged_attention import ops as paged_ops
    calls = []

    def record(table, lens):
        calls.append((np.asarray(table), np.asarray(lens)))

    def paged_attention(q, k_pages, v_pages, page_table, seq_lens, **kw):
        jax.debug.callback(record, page_table, seq_lens)
        return paged_ops.paged_attention_ref(q, k_pages, v_pages,
                                             page_table, seq_lens, **kw)

    monkeypatch.setattr(paged_ops, "use_pallas_kernel", lambda: True)
    monkeypatch.setattr(paged_ops, "paged_attention", paged_attention)
    srv = _server(tiny_model, batch_size=3, pipeline=pipeline)
    reqs = _submit(srv, [(8, 13), (4, 3), (12, 6), (4, 9)])
    _drive(srv, reqs)
    layers = tiny_model[0].cfg.num_layers
    assert len(calls) == srv.stats["steps"] * layers
    fetched = sum(
        int(pk.pages_fetched(table[:, 0], lens, PAGE, table.shape[1],
                             xp=np).sum()) for table, lens in calls)
    dead = [(table[:, 0] == 0) & (lens > 0) for table, lens in calls]
    assert any(d.any() for d in dead)     # a nulled row kept its length
    assert srv.stats["kv_pages_visited"] == fetched // layers
    assert 0 < srv.stats["kv_pages_live"] <= srv.stats["kv_pages_visited"]


# ---------------------------------------------------------------------------
# what holds the backlog head
# ---------------------------------------------------------------------------

def test_a_pool_too_small_for_two_reservations_blocks_on_pages(tiny_model):
    """Each request reserves 8 pages of a 9-page pool: the second waits
    with a slot free."""
    srv = _server(tiny_model, batch_size=2, num_pages=10, preempt=False)
    reqs = _submit(srv, [(4, 24), (4, 24)])
    _drive(srv, reqs)
    assert srv.stats["queue_blocked_pages_s"] > 0
    assert srv.stats["queue_blocked_slots_s"] == 0


def test_one_slot_with_a_large_pool_blocks_on_slots(tiny_model):
    srv = _server(tiny_model, batch_size=1)
    reqs = _submit(srv, [(4, 12), (4, 12)])
    _drive(srv, reqs)
    assert srv.stats["queue_blocked_slots_s"] > 0
    assert srv.stats["queue_blocked_pages_s"] == 0


# ---------------------------------------------------------------------------
# host spans in a profiler trace
# ---------------------------------------------------------------------------

def _stat(ev, key):
    return next((v for k, v in ev.stats if k == key), None)


def test_profiler_trace_holds_the_server_spans(tiny_model, tmp_path):
    from jax.profiler import ProfileData
    srv = _server(tiny_model)
    reqs = _submit(srv, [(6, 9), (8, 9)])
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drive(srv, reqs)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [ev for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:")
              for line in p.lines for ev in line.events
              if ev.name.startswith("serve.")]
    names = {ev.name.split("#")[0] for ev in events}
    assert names == {"serve.run_once", "serve.admit", "serve.dispatch",
                     "serve.harvest", "serve.sync"}
    uids = {_stat(ev, "uid") for ev in events
            if ev.name.startswith("serve.admit")}
    assert uids == {r.uid for r in reqs}
    blocks = sorted(_stat(ev, "block") for ev in events
                    if ev.name.startswith("serve.harvest"))
    assert blocks == list(range(srv.stats["blocks"]))
