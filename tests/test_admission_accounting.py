"""Property tests for paged admission accounting: worst-case page
reservations (``_worst_pages`` / ``_admission_pages_ready``), the
prefix-sharing eligibility rule (``_shareable_pages``) at page-boundary
and ``max_seq``-clamp edges, and the admission-ordering contract under
preemption churn (FIFO is never overtaken by preemption-freed pages;
victims always resume).  Pure host math — the churn harness drives the
REAL scheduler methods against fakes for the device-touching steps."""
import dataclasses
import functools
import queue as queue_mod

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import build_model, get_config
from repro.kernels.paged_attention.ops import BlockManager
from repro.memory import BlockPoolResidency
from repro.runtime.serve import BatchedServer, Request, _Preempted

MAX_SEQ = 64
PAGE = 4


@functools.lru_cache(maxsize=1)
def _server() -> BatchedServer:
    cfg = get_config("qwen2.5-14b").reduced()
    cfg = dataclasses.replace(cfg, remat=False, page_size=PAGE)
    model = build_model(cfg)
    return BatchedServer(model, model.init(jax.random.PRNGKey(0)),
                         batch_size=2, max_seq=MAX_SEQ, paged=True)


def _valid(plen: int, mnt: int) -> bool:
    """Would submit() accept this (prompt + decode budget fits)?"""
    return plen + max(mnt - 1, 0) <= MAX_SEQ


@given(plen=st.integers(1, MAX_SEQ), mnt=st.integers(0, MAX_SEQ))
@settings(max_examples=60, deadline=None)
def test_worst_pages_covers_every_write_and_respects_max_seq(plen, mnt):
    srv = _server()
    if not _valid(plen, mnt):
        return
    worst = srv._worst_pages(plen, mnt)
    plen_adm = srv._admit_plen(plen, mnt)
    # bucketing only ever pads the prompt, and never past the point
    # where a decode write could land outside the cache
    assert plen_adm >= plen
    assert plen_adm + max(mnt - 1, 0) <= MAX_SEQ or plen_adm == plen
    # the reservation covers the admitted prompt AND the whole decode
    # budget, clamped at max_seq (positions past it are never written)
    lifetime_tokens = min(plen_adm + max(mnt - 1, 0), MAX_SEQ)
    assert worst == srv.manager.pages_for(lifetime_tokens)
    assert worst <= srv.manager.pages_for(MAX_SEQ)      # max_seq clamp
    assert worst >= srv.manager.pages_for(plen)         # prompt fits


@pytest.mark.parametrize("plen,mnt", [
    (PAGE, 0), (PAGE, 1), (2 * PAGE, 0), (2 * PAGE, 1),     # page edges
    (PAGE + 1, 1), (MAX_SEQ, 1), (MAX_SEQ - 1, 2),          # clamp edges
])
def test_worst_pages_boundary_cases(plen, mnt):
    srv = _server()
    worst = srv._worst_pages(plen, mnt)
    plen_adm = srv._admit_plen(plen, mnt)
    assert worst == srv.manager.pages_for(
        min(plen_adm + max(mnt - 1, 0), MAX_SEQ))
    if mnt <= 1:
        # no decode writes beyond the sampled-at-admission token: the
        # reservation is exactly the admitted prompt's pages
        assert worst == srv.manager.pages_for(plen_adm)


@given(reqs=st.lists(st.integers(1, MAX_SEQ), min_size=1, max_size=24))
@settings(max_examples=30, deadline=None)
def test_admission_gate_never_oversubscribes(reqs):
    """Follow the gate exactly as _admit_from_queue does: a request is
    admitted only when its worst case fits beside every live
    reservation — so total reservations can never exceed capacity, and
    an admitted request can never hit mid-decode pool exhaustion."""
    srv = _server()
    srv._reserved = {}
    cap = srv.manager.capacity
    slot = 0
    for plen in reqs:
        mnt = (plen % 7) + 1                   # deterministic budget mix
        if not _valid(plen, mnt):
            continue
        req = Request(uid=slot, prompt=np.zeros(plen, np.int32),
                      max_new_tokens=mnt)
        if srv._admission_pages_ready(req):
            srv._reserved[slot] = srv._worst_pages(plen, mnt)
            slot += 1
        assert sum(srv._reserved.values()) <= cap
        if slot and slot % 5 == 0:             # periodic reclamation
            srv._reserved.pop(min(srv._reserved), None)
    srv._reserved = {}


@given(plen=st.integers(1, MAX_SEQ))
@settings(max_examples=40, deadline=None)
def test_shareable_pages_never_cover_a_written_position(plen):
    """Shared prompt pages must lie strictly before the last prompt
    token: admission always keeps at least one suffix token to prefill,
    and decode's first write (position >= plen) can never land in a
    shared page."""
    srv = _server()
    n = srv._shareable_pages(plen)
    assert n == (plen - 1) // PAGE             # maximal whole pages
    assert n * PAGE <= plen - 1                # excludes the last token
    # decode writes start at position >= plen, strictly past the shared
    # region [0, n*PAGE)
    assert n * PAGE < plen
    if plen % PAGE == 0:
        # page-boundary edge: the final FULL page still stays private
        assert n == plen // PAGE - 1


# ---------------------------------------------------------------------------
# admission ordering under preemption churn
# ---------------------------------------------------------------------------

class _SchedHarness(BatchedServer):
    """The REAL scheduler (``_admit_from_queue`` and the whole victim
    selection / resume-gating machinery run unmodified) over a real
    :class:`BlockManager`, with only the device-touching steps faked as
    host bookkeeping — so admission-ordering properties can be driven
    through thousands of churn schedules without a single dispatch."""

    def __init__(self, *, batch: int = 3, num_pages: int = 12,
                 policy: str = "lru"):
        # deliberately no super().__init__ — no model, no device state;
        # _init_sched_state is the scheduler's OWN definition of the
        # host state it needs, so the harness can never drift from it
        self.paged = True
        self.preempt_enabled = True
        self.preempt_policy = policy
        self.prefix_cache = False
        self.max_seq = MAX_SEQ
        self.batch = batch
        self.page_size = PAGE
        # host-only pool residency (no ledger): the scheduler's record()
        # calls run for real and record nothing
        self.kv = BlockPoolResidency(num_pages, PAGE)
        self.manager = self.kv.manager
        self.slots: list[Request | None] = [None] * batch
        self._init_sched_state(batch)
        self.events: list[tuple[str, int]] = []

    # ----- fakes for the device-touching steps -----------------------------
    def _admit(self, req: Request, slot: int) -> bool:
        self._reserved[slot] = self._worst_pages(len(req.prompt),
                                                 req.max_new_tokens)
        plen = self._admit_plen(len(req.prompt), req.max_new_tokens)
        self.manager.ensure(slot, plen)
        self.manager.note_tokens(slot, plen)
        req.pos = plen                               # host-side position
        req.output.append(0)                         # admission token
        self.slots[slot] = req
        self._last_sched[slot] = self._sched_counter
        self._sched_counter += 1
        self.events.append(("admit", req.uid))
        return False

    def _preempt_slot(self, i: int, finished: list[Request]) -> None:
        req = self.slots[i]
        self._preempted.append(_Preempted(req=req, pos=req.pos,
                                          handle=None, key=None))
        self.manager.free_slot(i)
        self._reserved.pop(i, None)
        self.slots[i] = None
        self.events.append(("preempt", req.uid))

    def _resume(self, ps: _Preempted, slot: int,
                finished: list[Request]) -> bool:
        self._reserved[slot] = self._resume_worst(ps)
        try:
            self.manager.ensure(slot, ps.pos)
        except MemoryError:
            self._reserved.pop(slot, None)
            return False
        self.manager.note_tokens(slot, ps.pos)
        self.slots[slot] = ps.req
        self._last_sched[slot] = self._sched_counter
        self._sched_counter += 1
        self.events.append(("resume", ps.req.uid))
        return True

    def _evict_slot(self, i: int) -> None:
        # the real one also deactivates the device slot; host-side the
        # page/reservation release is the whole story
        req = self.slots[i]
        self.manager.free_slot(i)
        self._reserved.pop(i, None)
        self.slots[i] = None
        self._planned[i] = 0
        self.events.append(("evict", req.uid))

    # ----- churn driver -----------------------------------------------------
    def decode_tick(self, finished: list[Request]) -> None:
        """One decode block's worth of host bookkeeping: every live slot
        emits a token (growing its pages on demand, as dispatch does)
        and finished slots reclaim.  Advances the server's block clock —
        deadlines and handoff leases run on it."""
        self.stats["blocks"] += 1
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.pos += 1
            req.output.append(0)
            self.manager.ensure(i, min(req.pos, self.max_seq))
            self.manager.note_tokens(i, min(req.pos, self.max_seq))
            if len(req.output) >= req.max_new_tokens:
                self.manager.free_slot(i)
                self._reserved.pop(i, None)
                self.slots[i] = None
                self._finalize(req, "completed", finished)
                self.events.append(("finish", req.uid))

    def check_invariants(self) -> None:
        self.manager.audit()
        assert sum(self._reserved.values()) <= self.manager.capacity, \
            (self._reserved, self.manager.capacity)
        # every live slot's remaining lifetime is covered by its
        # reservation (the no-mid-decode-exhaustion guarantee)
        for i, req in enumerate(self.slots):
            if req is not None:
                assert len(self.manager.slot_pages(i)) <= self._reserved[i]
        # ...and every in-flight prefill's pages by its pseudo-slot
        # reservation, held in full from the moment it STARTED
        if self.prefill is not None:
            for inf in self.prefill.inflight:
                assert len(self.manager.slot_pages(inf.slot)) \
                    <= self._reserved[inf.slot], (inf.slot, self._reserved)


def _run_churn(shapes: list[tuple[int, int]], schedule: list[int],
               policy: str = "lru") -> _SchedHarness:
    srv = _SchedHarness(policy=policy)
    pending = [Request(uid=u, prompt=np.zeros(p, np.int32),
                       max_new_tokens=m)
               for u, (p, m) in enumerate(shapes)
               if p + max(m - 1, 0) <= MAX_SEQ]
    for r in pending:
        r.pos = 0
    todo = list(pending)
    finished: list[Request] = []
    for op in schedule:
        if op == 0 and todo:
            srv.queue.put(todo.pop(0))
        else:
            srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    while todo:                               # drain: submit stragglers...
        srv.queue.put(todo.pop(0))
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    for _ in range(400):                      # ...then decode to done
        if len(finished) == len(pending):
            break
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    assert len(finished) == len(pending), (
        f"starved: {len(finished)}/{len(pending)} finished, "
        f"preempted={[(p.req.uid) for p in srv._preempted]}, "
        f"backlog={[r.uid for r in srv._backlog]}, events={srv.events}")
    return srv


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=10),
       schedule=st.lists(st.integers(0, 1), min_size=10, max_size=80))
@settings(max_examples=40, deadline=None)
def test_admission_fifo_never_overtaken_by_preemption(shapes, schedule):
    """Under arbitrary submit/decode interleavings with preemption on,
    first-time admission stays strictly FIFO: pages freed by preempting
    a victim admit the backlog HEAD, never a younger request — and no
    request starves (every victim resumes and finishes)."""
    srv = _run_churn(shapes, schedule)
    first_admits = [uid for kind, uid in srv.events if kind == "admit"]
    assert first_admits == sorted(first_admits), srv.events
    assert len(set(first_admits)) == len(first_admits)
    # a preempted uid always resumes (and may be preempted again, but
    # its resume count keeps up: no victim is left swapped out)
    assert not srv._preempted
    for uid in {u for k, u in srv.events if k == "preempt"}:
        kinds = [k for k, u in srv.events if u == uid]
        assert kinds.count("resume") == kinds.count("preempt"), srv.events
        assert kinds[-1] == "finish"


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=8),
       schedule=st.lists(st.integers(0, 1), min_size=10, max_size=60),
       policy=st.sampled_from(["fewest_pages", "lowest_progress"]))
@settings(max_examples=15, deadline=None)
def test_admission_ordering_holds_for_every_victim_policy(shapes, schedule,
                                                          policy):
    """The FIFO/no-starvation contract is policy-independent: victim
    selection changes WHO pays for the head's admission, never who
    admits next."""
    srv = _run_churn(shapes, schedule, policy=policy)
    first_admits = [uid for kind, uid in srv.events if kind == "admit"]
    assert first_admits == sorted(first_admits), srv.events
    assert not srv._preempted


def test_resume_fifo_beats_backlog():
    """A swapped-out victim is older than every queued request: when
    pages free up, the victim resumes BEFORE the backlog head admits."""
    srv = _SchedHarness()
    finished: list[Request] = []
    reqs = [Request(uid=u, prompt=np.zeros(4, np.int32), max_new_tokens=10)
            for u in range(4)]
    for r in reqs:
        r.pos = 0
        srv.queue.put(r)
    srv._admit_from_queue(finished, allow_preempt=True)
    assert [k for k, _ in srv.events].count("admit") >= 2
    # force a preemption for the head, then finish a live slot: the
    # resulting free pages must go to the victim first
    while not any(k == "preempt" for k, _ in srv.events):
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        if len(finished) == len(reqs):
            pytest.skip("pool large enough that nothing preempted")
    victim = next(u for k, u in srv.events if k == "preempt")
    for _ in range(400):
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        if len(finished) == len(reqs):
            break
    ev = srv.events
    resume_at = ev.index(("resume", victim))
    later_admits = [u for k, u in ev[resume_at:] if k == "admit"]
    preempt_at = ev.index(("preempt", victim))
    admits_between = [u for k, u in ev[preempt_at:resume_at] if k == "admit"]
    # only the head the victim was preempted FOR may admit in between
    assert len(admits_between) <= 1, ev
    assert all(u > victim for u in admits_between + later_admits), ev
    assert len(finished) == len(reqs)


# ---------------------------------------------------------------------------
# admission fairness under ASYNC prefill (the disaggregated engine)
# ---------------------------------------------------------------------------

class _HostPrefillEngine:
    """Host-bookkeeping double of :class:`repro.runtime.prefill
    .PrefillEngine` exposing the exact scheduling surface
    ``_async_admission`` drives (``start`` / ``pump_once`` / ``ready``
    / ``inflight`` / ``idle``) over the REAL BlockManager and the REAL
    reservation dict — only the prefill dispatch and the staging
    round-trip are faked."""

    @dataclasses.dataclass
    class _Inflight:
        req: Request
        slot: int
        plen: int
        done: int
        toks: np.ndarray = None          # padded prompt (prefix sharing)

    @dataclasses.dataclass
    class _Handoff:
        req: Request
        plen: int
        token: int
        pslot: int
        lease_expiry_block: int = 0
        handle: object = None            # no staged bytes host-side

    def __init__(self, srv, *, chunk_tokens: int = PAGE, max_inflight=2):
        import collections
        self.srv = srv
        self.chunk_tokens = chunk_tokens
        self.max_inflight = max_inflight
        self.inflight: list[_HostPrefillEngine._Inflight] = []
        self.ready = collections.deque()
        self._rr = 0

    @property
    def idle(self):
        return not self.inflight and not self.ready

    def crash(self) -> None:
        """Mirror of PrefillEngine.crash: in-flight prefills orphan
        their partial pages, staged handoffs keep their leases."""
        srv = self.srv
        for inf in self.inflight:
            srv._orphan_prefills.append((inf.slot, inf.req))
        self.inflight.clear()
        while self.ready:
            srv._orphan_handoffs.append(self.ready.popleft())
        srv.stats["engine_crashes"] += 1
        srv.events.append(("crash", -1))

    def start(self, req: Request) -> None:
        srv = self.srv
        slot = -1000 - req.uid
        srv._reserved[slot] = srv._worst_pages(len(req.prompt),
                                               req.max_new_tokens)
        plen = srv._admit_plen(len(req.prompt), req.max_new_tokens)
        toks = np.zeros((1, plen), np.int32)
        toks[0, plen - len(req.prompt):] = req.prompt
        shared = (srv._shared_prefix_pages(toks, plen)
                  if srv.prefix_cache else [])
        if shared:
            srv.manager.adopt(slot, shared)
            srv.stats["prefix_hits"] += 1
            srv.stats["prefix_shared_pages"] += len(shared)
        self.inflight.append(self._Inflight(req, slot, plen,
                                            len(shared) * PAGE, toks))
        srv.events.append(("start", req.uid))

    def pump_once(self, finished: list) -> bool:
        if not self.inflight:
            return False
        srv = self.srv
        inf = self.inflight[self._rr % len(self.inflight)]
        self._rr += 1
        chunk = min(self.chunk_tokens, inf.plen - inf.done)
        try:
            srv.manager.ensure(inf.slot, inf.done + chunk)
        except MemoryError:
            return False
        inf.done += chunk
        srv.manager.note_tokens(inf.slot, inf.done)
        if inf.done >= inf.plen:
            self.inflight.remove(inf)
            if srv.prefix_cache:
                srv._register_prefix(inf.toks, inf.plen, inf.slot)
            tok = srv.manager.detach_to_handoff(inf.slot)
            self.ready.append(self._Handoff(
                inf.req, inf.plen, tok, inf.slot,
                lease_expiry_block=(srv.stats["blocks"]
                                    + srv.handoff_lease_blocks)))
            srv.events.append(("handoff", inf.req.uid))
        return True


class _AsyncSchedHarness(_SchedHarness):
    """The REAL ``_async_admission`` loop (FIFO starts behind the page
    gate, one pump per round, handoff adoption) over the host engine —
    with only :meth:`_adopt_handoff`'s device splice faked."""

    def __init__(self, *, chunk_tokens: int = PAGE, **kw):
        super().__init__(**kw)
        self.prefill = _HostPrefillEngine(self, chunk_tokens=chunk_tokens)

    def _adopt_handoff(self, h, slot: int, finished: list) -> None:
        self.manager.adopt_from_handoff(slot, h.token)
        self._reserved[slot] = self._reserved.pop(h.pslot)
        h.req.pos = h.plen
        h.req.output.append(0)                       # first token
        self.slots[slot] = h.req
        self._sched_counter += 1
        self._last_sched[slot] = self._sched_counter
        self.events.append(("admit", h.req.uid))


def _run_async_churn(shapes: list[tuple[int, int]], schedule: list[int],
                     **kw) -> _AsyncSchedHarness:
    srv = _AsyncSchedHarness(**kw)
    pending = [Request(uid=u, prompt=np.zeros(p, np.int32),
                       max_new_tokens=m)
               for u, (p, m) in enumerate(shapes)
               if p + max(m - 1, 0) <= MAX_SEQ]
    for r in pending:
        r.pos = 0
    todo = list(pending)
    finished: list[Request] = []
    for op in schedule:
        if op == 0 and todo:
            srv.queue.put(todo.pop(0))
        else:
            srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    while todo:
        srv.queue.put(todo.pop(0))
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    for _ in range(600):
        if len(finished) == len(pending):
            break
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    assert len(finished) == len(pending), (
        f"starved: {len(finished)}/{len(pending)} finished, "
        f"inflight={[i.req.uid for i in srv.prefill.inflight]}, "
        f"ready={[h.req.uid for h in srv.prefill.ready]}, "
        f"backlog={[r.uid for r in srv._backlog]}, events={srv.events}")
    return srv


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=10),
       schedule=st.lists(st.integers(0, 1), min_size=10, max_size=80))
@settings(max_examples=40, deadline=None)
def test_async_prefill_starts_stay_fifo_and_nothing_starves(shapes,
                                                            schedule):
    """Async-engine admission under churn: prefill STARTS are strictly
    FIFO (the page gate never lets a younger request overtake the
    backlog head), completions may land out of order, every request
    still finishes, and the allocator + reservation invariants hold
    after every step."""
    srv = _run_async_churn(shapes, schedule)
    starts = [uid for kind, uid in srv.events if kind == "start"]
    assert starts == sorted(starts), srv.events
    assert len(set(starts)) == len(starts)
    assert srv.prefill.idle and not srv._preempted
    # every started prefill handed off and adopted exactly once
    for uid in starts:
        kinds = [k for k, u in srv.events if u == uid]
        assert kinds.count("handoff") == 1, srv.events
        assert kinds.count("admit") == 1, srv.events


def test_out_of_order_completion_cannot_starve_earlier_start():
    """With decode work pending (one chunk per scheduling round), a
    long prompt starts prefilling FIRST; a short one behind it
    completes first and adopts the only free slot — the long prompt's
    worst-case reservation (held since its start) must survive the
    overtaking completion, so it always finishes."""
    srv = _AsyncSchedHarness(batch=2, num_pages=40, chunk_tokens=PAGE)
    finished: list[Request] = []
    # a steady decoder keeps decode dispatchable for the whole churn —
    # otherwise the idle-burst path batches both prefills before any
    # adoption and there is no overtaking to observe
    steady = Request(uid=9, prompt=np.zeros(2, np.int32),
                     max_new_tokens=40)
    steady.pos = 0
    srv.queue.put(steady)
    srv._admit_from_queue(finished, allow_preempt=True)
    srv.check_invariants()
    assert ("admit", 9) in srv.events and srv._can_dispatch()
    long_req = Request(uid=0, prompt=np.zeros(24, np.int32),
                       max_new_tokens=4)
    short_req = Request(uid=1, prompt=np.zeros(4, np.int32),
                        max_new_tokens=4)
    for r in (long_req, short_req):
        r.pos = 0
        srv.queue.put(r)
    long_pslot, long_worst = -1000, srv._worst_pages(24, 4)
    # one chunk advances per round while decode is pending; the short
    # prompt (1 chunk) completes long before the long one (6 chunks)
    for _ in range(40):
        if ("admit", 1) in srv.events:
            break
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        srv.decode_tick(finished)
    starts = [u for k, u in srv.events if k == "start"]
    assert starts == [9, 0, 1]                     # FIFO starts
    # the short prompt overtook the long one to the handoff AND the
    # slot...
    assert ("handoff", 1) in srv.events
    assert ("handoff", 0) not in srv.events    # long still mid-prefill
    assert ("admit", 1) in srv.events
    assert ("admit", 0) not in srv.events
    # ...but the long prompt's start-time reservation is still pinned
    # under its pseudo-slot at full worst case — the overtaker spent
    # its own budget, not the head's
    assert srv._reserved.get(long_pslot) == long_worst
    while ("admit", 0) not in srv.events:
        assert srv._reserved.get(long_pslot) == long_worst
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    for _ in range(200):
        if len(finished) == 3:
            break
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    assert {r.uid for r in finished} == {0, 1, 9}, srv.events


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=8),
       schedule=st.lists(st.integers(0, 1), min_size=10, max_size=60),
       policy=st.sampled_from(["fewest_pages", "lowest_progress"]))
@settings(max_examples=15, deadline=None)
def test_async_prefill_fairness_holds_under_preemption(shapes, schedule,
                                                       policy):
    """Preemption churn + async engine: victim selection changes who
    pays for the head's pages, never the FIFO start order — and every
    victim resumes."""
    srv = _run_async_churn(shapes, schedule, policy=policy)
    starts = [uid for kind, uid in srv.events if kind == "start"]
    assert starts == sorted(starts), srv.events
    assert not srv._preempted
    for uid in {u for k, u in srv.events if k == "preempt"}:
        kinds = [k for k, u in srv.events if u == uid]
        assert kinds.count("resume") == kinds.count("preempt"), srv.events


# ---------------------------------------------------------------------------
# request lifecycle: engine crashes, handoff leases, deadlines, overload
# ---------------------------------------------------------------------------

def _assert_fully_reclaimed(srv) -> None:
    """Zero-leak postcondition after a full drain: allocator audit
    clean, no page allocated anywhere (handoffs included), no dangling
    reservation or crash-recovery state, pending demand view empty."""
    srv.manager.audit()
    assert srv.manager.pages_in_use == 0, srv.manager.pages
    assert srv.manager.handoff_pages == 0
    assert not srv._reserved, srv._reserved
    assert not srv._orphan_prefills and not srv._orphan_handoffs
    assert srv._pending_count == 0 and srv._pending_pages == 0


def _drive_to_drain(srv, pending, finished, rounds=800) -> None:
    for _ in range(rounds):
        if all(r.done.is_set() for r in pending):
            break
        srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    assert all(r.done.is_set() for r in pending), (
        f"wedged: {[r.uid for r in pending if not r.done.is_set()]}, "
        f"events={srv.events}")


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=8),
       schedule=st.lists(st.integers(0, 1), min_size=6, max_size=40),
       crash_round=st.integers(0, 45),
       lease=st.integers(1, 8),
       share=st.booleans())
@settings(max_examples=40, deadline=None)
def test_prefill_crash_reclaims_requeues_and_leaks_nothing(
        shapes, schedule, crash_round, lease, share):
    """Crash the prefill engine at an arbitrary churn point — mid-chunk
    prefills and staged (possibly prefix-sharing) handoffs alike.  The
    watchdog must reclaim every orphaned page (partial prefills at
    once, staged handoffs after their lease) and requeue the victims;
    every request still finishes, with the allocator audit clean after
    every step and zero pages/reservations/pending leaked at the end."""
    srv = _AsyncSchedHarness()
    srv.prefix_cache = share
    srv.handoff_lease_blocks = lease
    pending = [Request(uid=u, prompt=np.arange(p, dtype=np.int32) % 7,
                       max_new_tokens=m)
               for u, (p, m) in enumerate(shapes)
               if p + max(m - 1, 0) <= MAX_SEQ]
    for r in pending:
        r.pos = 0
    todo = list(pending)
    finished: list[Request] = []
    for rnd, op in enumerate(schedule + [1] * (crash_round + 1)):
        if rnd == crash_round:
            srv.prefill.crash()
        if op == 0 and todo:
            srv.queue.put(todo.pop(0))
        else:
            srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    while todo:
        srv.queue.put(todo.pop(0))
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    _drive_to_drain(srv, pending, finished)
    for r in pending:       # a crash sheds nothing: every victim retried
        assert r.error is None, r.error
    if ("crash", -1) in srv.events:
        assert srv.stats["engine_crashes"] == 1
    _assert_fully_reclaimed(srv)


def test_lease_expiry_reclaims_staged_handoff_and_retries():
    """A handoff staged while every decode slot is busy must not pin
    its pool pages forever: once its lease runs out the watchdog
    releases the registry entry and requeues the victim, which later
    admits normally and finishes."""
    srv = _AsyncSchedHarness(batch=2, num_pages=40)
    srv.handoff_lease_blocks = 3
    finished: list[Request] = []
    # two long decoders occupy both slots for many blocks
    busy = [Request(uid=u, prompt=np.zeros(2, np.int32), max_new_tokens=30)
            for u in (0, 1)]
    late = Request(uid=2, prompt=np.zeros(4, np.int32), max_new_tokens=4)
    for r in busy + [late]:
        r.pos = 0
    for r in busy:
        srv.queue.put(r)
    srv._admit_from_queue(finished, allow_preempt=True)
    assert all(s is not None for s in srv.slots)
    srv.queue.put(late)
    # pump the prefill to a staged handoff (no free slot to adopt into),
    # then sit past the lease: the watchdog must reclaim + requeue
    for _ in range(10):
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        if srv.stats["lease_reclaims"]:
            break
        srv.decode_tick(finished)
    assert srv.stats["lease_reclaims"] >= 1, srv.events
    assert srv.stats["crash_requeues"] >= 1
    assert srv.manager.handoff_pages == 0       # registry entry released
    _drive_to_drain(srv, busy + [late], finished)
    assert late.error is None and len(late.output) == late.max_new_tokens
    _assert_fully_reclaimed(srv)


def test_lease_reclaim_of_prefix_sharing_handoff_keeps_sharer_pages():
    """Lease-expiry x prefix-sharing: reclaiming an orphaned handoff
    whose leading pages are SHARED only drops the handoff's reference —
    the live sharer keeps decoding on intact pages (audit-verified)."""
    srv = _AsyncSchedHarness(batch=2, num_pages=40)
    srv.prefix_cache = True
    srv.handoff_lease_blocks = 2
    finished: list[Request] = []
    prompt = np.arange(3 * PAGE, dtype=np.int32)    # 2 shareable pages
    first = Request(uid=0, prompt=prompt.copy(), max_new_tokens=24)
    second = Request(uid=1, prompt=prompt.copy(), max_new_tokens=4)
    blocker = Request(uid=2, prompt=np.zeros(2, np.int32),
                      max_new_tokens=24)
    for r in (first, second, blocker):
        r.pos = 0
    # first publishes its prefix pages and decodes; blocker takes the
    # other slot so second's handoff has nowhere to land
    for r in (first, blocker):
        srv.queue.put(r)
    for _ in range(6):
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        if all(s is not None for s in srv.slots):
            break
        srv.decode_tick(finished)
    assert all(s is not None for s in srv.slots)
    srv.queue.put(second)
    for _ in range(12):
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
        if srv.stats["lease_reclaims"]:
            break
        srv.decode_tick(finished)
    assert srv.stats["lease_reclaims"] >= 1, srv.events
    # the handoff really adopted first's published pages, and the
    # reclaim gave back only the handoff's reference — first is still
    # live on intact pages
    assert srv.stats["prefix_hits"] >= 1, srv.events
    assert first in srv.slots
    srv.manager.audit()
    _drive_to_drain(srv, [first, second, blocker], finished)
    assert all(r.error is None for r in (first, second, blocker))
    _assert_fully_reclaimed(srv)


@given(shapes=st.lists(st.tuples(st.integers(1, 12), st.integers(2, 12)),
                       min_size=3, max_size=8),
       schedule=st.lists(st.integers(0, 1), min_size=6, max_size=40),
       deadlines=st.lists(st.one_of(st.none(), st.integers(0, 12)),
                          min_size=8, max_size=8),
       asynchronous=st.booleans())
@settings(max_examples=40, deadline=None)
def test_deadline_expiry_at_any_stage_reclaims_everything(
        shapes, schedule, deadlines, asynchronous):
    """Random tight deadlines across random churn hit requests at every
    lifecycle stage — backlogged, mid-prefill, staged handoff, live
    decode slot, preempted.  Every request must terminate (expired or
    served), every expiry must carry the structured error, and the
    allocator must end fully reclaimed."""
    srv = (_AsyncSchedHarness() if asynchronous else _SchedHarness())
    pending = [Request(uid=u, prompt=np.zeros(p, np.int32),
                       max_new_tokens=m)
               for u, (p, m) in enumerate(shapes)
               if p + max(m - 1, 0) <= MAX_SEQ]
    for i, r in enumerate(pending):
        r.pos = 0
        r.deadline_blocks = deadlines[i % len(deadlines)]
        r.submitted_block = 0
    todo = list(pending)
    finished: list[Request] = []
    for op in schedule:
        if op == 0 and todo:
            srv.queue.put(todo.pop(0))
        else:
            srv.decode_tick(finished)
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    while todo:
        srv.queue.put(todo.pop(0))
        srv._admit_from_queue(finished, allow_preempt=True)
        srv.check_invariants()
    _drive_to_drain(srv, pending, finished)
    for r in pending:
        if r.outcome == "expired":
            assert r.error is not None
            assert r.error["reason"] == "deadline_expired"
        else:
            assert r.error is None
    _assert_fully_reclaimed(srv)


def test_overload_gate_rejects_fast_and_counts_outcomes():
    """submit() under admission control: beyond ``max_pending`` /
    ``overload_factor`` requests come back instantly with
    ``outcome == "rejected"`` and a structured error; the admitted ones
    all complete and the outcome counters add up."""
    srv = _SchedHarness(num_pages=12)
    srv.max_pending = 3
    srv.overload_factor = 1.5
    reqs = [srv.submit(np.zeros(4, np.int32), max_new_tokens=4)
            for _ in range(10)]
    rejected = [r for r in reqs if r.outcome == "rejected"]
    admitted = [r for r in reqs if r.outcome != "rejected"]
    assert rejected and admitted
    for r in rejected:
        assert r.done.is_set()
        assert r.error["reason"] == "admission_rejected"
        assert not r.output
    for r in admitted:        # host-side position for the churn driver
        r.pos = 0
    finished: list[Request] = []
    srv._admit_from_queue(finished, allow_preempt=True)
    _drive_to_drain(srv, admitted, finished)
    assert srv.stats["rejected"] == len(rejected)
    assert all(r.error is None for r in admitted)
    _assert_fully_reclaimed(srv)
    # headroom restored: a fresh request is accepted again
    again = srv.submit(np.zeros(4, np.int32), max_new_tokens=4)
    assert again.outcome != "rejected"
