"""Serving runtime: fused on-device block decode + continuous batching
over a block-pool paged KV cache.

The decode hot path is ONE dispatch per ``block_size`` tokens: a
``lax.scan`` decode loop (:func:`repro.models.transformer.decode_loop`)
emits a ``(B, block)`` token block with per-slot ``active``/``remaining``
masks, the KV cache and decode state are **donated** into every dispatch
(updated in place, never copied), and the host syncs once per block to
harvest tokens.  On top of it, :class:`BatchedServer` does continuous
batching: requests are admitted into individual slots between blocks —
no batch restart — and slots are recycled the moment a sequence hits EOS
or its token budget.

For models that support it (dense-family transformers with full causal
attention) the KV cache is a **device-resident block page pool** instead
of a dense ``(L, B, Hkv, max_seq, hd)`` slab: fixed-size pages allocated
on demand at block boundaries by a host-side :class:`BlockManager` and
reclaimed on EOS/eviction, with prefill writing straight into freshly
allocated pages and decode attention reading only the pages each slot's
table maps.  On TPU the Pallas ``paged_attention`` kernel fetches and
attends only a live slot's pages below its length, in blocks of up to 8
pages, and nothing for a slot whose row is nulled, so its per-step cost
follows the live KV rather than ``batch × table width``; elsewhere the
gather oracle reads the whole table.  KV memory scales with live tokens
rather than ``batch × max_seq`` — while emitting bit-identical tokens to
the dense path.

Three structures close the paged-vs-dense throughput gap:

* **Device-resident page tables.**  The (B, n_pages) table persists in
  ``DecodeState.pages`` across dispatches; the host keeps a byte-exact
  mirror and ships only the per-block *delta* (entries for slots that
  crossed a page boundary, were admitted, or were evicted — an evicted
  row is re-pointed at the null page so the dead slot's frozen-position
  writes stay harmless), applied inside the decode dispatch with one
  scatter.  The table width is
  power-of-two bucketed and the full table is re-transferred only when
  the width changes, so executable count stays O(log max_pages) over a
  server's lifetime (``stats["compiles"]`` / ``stats["table_rebuilds"]``).
* **Async double-buffered dispatch.**  ``run_once`` keeps up to two
  decode blocks in flight: block N+1 is dispatched — page growth folded
  into its delta — before block N's token harvest is synced, so host
  scheduling overlaps device compute instead of serializing
  dispatch→sync→schedule.  Donation keeps exactly two state buffers
  alive.  Speculative page allocation is safe because admission reserves
  every request's worst-case page count up front.
* **Prefix caching.**  Requests whose padded prompts share leading whole
  pages map those table entries to the same physical pages (per-page
  refcounts in :class:`BlockManager`; a prompt-prefix hash index keyed
  by exact token bytes).  Admission then prefills only the suffix —
  bit-identical to a full prefill — cutting both prefill FLOPs and pool
  residency by roughly the share ratio.  Divergence after the shared
  prefix is copy-on-write by construction: the first partial (or
  non-matching) page is always a private page, and shared pages are
  never written after registration.

``serve_step`` (one per-token dispatch) is kept for dry-run lowering and
as the baseline the serving benchmark measures against.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import queue
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import memory
from repro.kernels.paged_attention.kernel import pages_fetched
from repro.memory import MemoryOrchestrator
from repro.memory import tiers as memtiers
from repro.memory.swap import PageSwapper, SwapHandle
from repro.models.base import DecodeState
from repro.models.transformer import (decode_loop, sample_tokens,
                                      vocab_mask_logits)
from repro.runtime.ft import StragglerMonitor
from repro.runtime.sharding import (activate_mesh, gather_tp_mode,
                                    mesh_axis_sizes, replicated)

# Single source of truth for the logits -> token step; the old
# ``serve.sample`` duplicate of ``transformer.sample_tokens`` is gone.
sample = sample_tokens


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    output: list = dataclasses.field(default_factory=list)
    # structured degradation outcome: None on success, else a dict with
    # at least {"reason", "detail"} when the server terminated the
    # request instead of completing it (unrecoverable tier fault, pool
    # exhaustion with no victim, admission rejection, expired deadline,
    # poisoned logits).  ``done`` is set either way.
    error: dict | None = None
    admitted_at_block: int | None = None   # stats["blocks"] at admission
    # TTFT instrumentation, in decode-block units (the server's clock):
    # stats["blocks"] when the request entered the queue and when its
    # first token was produced (admission prefill or handoff adoption)
    submitted_block: int | None = None
    first_token_block: int | None = None
    # SLA deadline, in decode-block units relative to submitted_block:
    # the request is cancelled at whatever lifecycle stage it is in —
    # queued, backlogged, mid-prefill, mid-decode, preempted-and-swapped
    # — once ``deadline_blocks`` blocks elapse without completion.
    # None = no deadline.
    deadline_blocks: int | None = None
    # terminal outcome, stamped exactly once by BatchedServer._finalize:
    # "completed" | "shed" | "rejected" | "expired" (None = in flight)
    outcome: str | None = None
    # per emitted token, the (V,) logits it was sampled from — filled
    # only by a server built with ``record_logits=True``
    logits: list = dataclasses.field(default_factory=list)
    # host clock (time.perf_counter): when submit() queued the request,
    # and when it left the backlog for its first prefill (monolithic
    # admission or the prefill engine's start; a resume keeps it)
    t_submit: float | None = None
    t_admit: float | None = None
    _pending_counted: bool = dataclasses.field(default=False, repr=False)


@dataclasses.dataclass
class _Preempted:
    """A sequence swapped out of the live batch: its request, the decode
    position it will resume from, the KV stash (``handle.tier`` says
    which hierarchy level it currently occupies), and its per-request
    PRNG key (so resumed sampling is bit-identical)."""

    req: Request
    pos: int
    handle: SwapHandle
    key: np.ndarray                  # (2,) uint32
    # stats["blocks"] when the stash was created — the cold-park sweep's
    # age clock (stash age = blocks - stashed_block)
    stashed_block: int = 0


def make_prefill_step(model) -> Callable:
    def prefill_step(params, tokens, cache, extra=None):
        logits, cache = model.prefill(params, tokens, cache, extra)
        return logits, cache
    return prefill_step


def make_serve_step(model, *, temperature: float = 0.0) -> Callable:
    """One decode step: (params, tokens (B,1), cache, cur_pos, key) ->
    (next_tokens (B,1), logits, cache).  The per-token baseline."""
    vocab = model.cfg.vocab

    def serve_step(params, tokens, cache, cur_pos, key):
        logits, cache = model.decode_step(params, tokens, cache, cur_pos)
        nxt = sample(logits, vocab, temperature, key)
        return nxt, logits, cache
    return serve_step


def make_decode_loop(model, *, block_size: int, temperature: float = 0.0,
                     eos_id: int | None = None, donate: bool = True,
                     detect_nonfinite: bool = False,
                     emit_logits: bool = False) -> Callable:
    """Jit the fused decode loop with the donation contract: the cache
    (arg 1) and decode state (arg 2) are consumed by every dispatch.

    ``delta`` (optional) is a ``(slots, cols, pids)`` int32 triple of
    page-table updates applied to the device-resident table with ONE
    scatter before the block decodes — the host never re-transfers the
    whole table on the steady-state path.  Padding entries carry an
    out-of-range column and are dropped by the scatter.

    ``detect_nonfinite=True`` (the server's setting) adds the per-slot
    poison mask to the returned tuple — see
    :func:`repro.models.transformer.decode_loop` — so a NaN in one
    sequence's logits sheds that sequence at harvest instead of
    silently corrupting its stream."""
    def loop(params, cache, state, delta=None):
        if delta is not None and state.pages is not None:
            d_slots, d_cols, d_pids = delta
            state = dataclasses.replace(
                state, pages=state.pages.at[d_slots, d_cols].set(d_pids))
        return decode_loop(model, params, cache, state, num_steps=block_size,
                           temperature=temperature, eos_id=eos_id,
                           detect_nonfinite=detect_nonfinite,
                           emit_logits=emit_logits)
    return model.mem.donating_jit(loop,
                                  donate_argnums=(1, 2) if donate else ())


def _bucket(n: int, quantum: int = 8) -> int:
    """Pad lengths to a bucket so admission compiles O(log) shapes."""
    b = quantum
    while b < n:
        b *= 2
    return b


class BatchedServer:
    """Continuous-batching inference server (single process).

    Decode runs in fixed-size fused blocks over a persistent ``batch_size``
    -slot state.  Between blocks, finished slots are recycled and queued
    requests are admitted into the live cache — mid-stream, without
    restarting or re-prefilling the rest of the batch.  Exactly one host
    transfer happens per decoded block (the token-block harvest).

    ``paged`` (default: auto) selects the block-pool paged KV cache when
    the model supports it.  ``num_pages`` sizes the pool — the default
    matches dense capacity (``batch × ceil(max_seq/page)`` plus the null
    page), so admission never blocks; smaller pools oversubscribe and
    engage **page-granular preemption** (``preempt``, default on): when
    the backlog head would starve, victim sequences chosen by
    ``preempt_policy`` (``"lru"`` / ``"fewest_pages"`` /
    ``"lowest_progress"`` / a callable) have their KV pages swapped to
    the remote tier by a :class:`~repro.memory.swap.PageSwapper`, their
    physical pages freed, and are transparently restored — resume-FIFO
    ahead of the backlog — when pages free up again.  Per-slot PRNG keys
    (``fold_in(request key, position)``) make a preempted+resumed
    sequence emit bit-identical tokens to an unpreempted run at any
    temperature.  Tier transfers retry with exponential backoff under an
    installed :class:`~repro.memory.tiers.FaultPlan`; unrecoverable
    faults degrade per policy (victim shed with a structured
    ``Request.error``, prefix sharing dropped under pool pressure,
    injected mid-decode exhaustion recovered by emergency preemption).
    ``audit`` (or ``REPRO_AUDIT=1``) cross-checks the block-pool
    invariants after every scheduling step.

    ``pipeline`` (default on) keeps up to two decode blocks in flight so
    host scheduling overlaps device compute; tokens are bit-identical to
    the serialized loop (the device-side masks decide everything), only
    the block/admission interleaving — and hence sampled tokens of
    requests admitted mid-stream at temperature > 0 — can shift.
    ``prefix_cache`` (default on, paged only) shares prompt-prefix pages
    across requests via per-page refcounts.

    ``prefill_async`` (default off, paged only) disaggregates serving
    into a prefill engine and a decode engine communicating only
    through KV pages staged in the remote tier (see
    :mod:`repro.runtime.prefill`): prompts prefill asynchronously in
    page-aligned ``prefill_chunk_tokens`` chunks and finished prompts
    are adopted as page handoffs, so a long prompt arriving mid-stream
    stalls decode by at most one chunk instead of its whole length —
    with bit-identical tokens at any temperature
    (``stats["decode_stall_blocks_max"]`` /
    ``stats["ttft_p50_blocks"]`` quantify the interference).

    ``mesh`` (default None = single device) turns on tensor-parallel
    serving: params are placed by ``runtime.sharding.named_shardings``
    over the model's ``serving_param_specs()`` (pageable groups in the
    remote tier when the pager is on), the KV cache — dense slab or
    page pools — is sharded over the ``"model"`` axis by KV heads, the
    decode state and page tables are replicated, and every dispatch is
    traced under the mesh so the model-side constraint specs resolve.
    Tokens are bit-identical to the single-device server at any
    temperature **because serving TP is all-gather based**: activations
    are replicated before the attention/MLP output projections and
    those weights stay replicated, so every cross-device transfer is
    pure data movement and every dot runs full-width exactly as on one
    device.  (Partial-sum row-parallel TP is NOT safe here: each
    shard's partial rounds separately and flips greedy ties — that path
    is kept for training only.)  Models without ``serving_param_specs``
    are rejected rather than served with silently diverging tokens.

    ``record_logits=True`` keeps, per request, the logits every emitted
    token was sampled from (``Request.logits``) — what a correctness
    check compares against a reference forward.  It costs one host copy
    of ``(batch, block, vocab)`` logits per block.

    ``deterministic=False`` opts OUT of that contract for raw speed:
    the output projections keep their Megatron row-parallel contraction
    shard (plain ``param_specs``) and the all-gather constraints stay
    disarmed, so XLA lowers a partial-sum all-reduce per projection —
    less wire per step on wide models, but each shard's partials round
    separately, so tokens may differ from the single-device server
    (greedy ties can flip).  Single-run determinism is preserved; only
    cross-placement bit-identity is traded away.
    """

    # async prefill engine (repro.runtime.prefill.PrefillEngine) or None
    # (monolithic admission); class defaults so scheduler-only harness
    # subclasses that skip __init__ resolve the monolithic / host-only
    # paths (kv/manager/swapper are rebound by _init_live_state or the
    # harness itself)
    prefill = None
    kv = None
    manager = None
    swapper = None
    # overload admission control (None = unbounded, the pre-SLA
    # behavior): max_pending caps queued+backlogged requests;
    # overload_factor caps the MemoryLedger-projected worst-case page
    # demand (live reservations + pending) at overload_factor x pool
    # capacity — beyond either, submit() returns a fast structured
    # rejection instead of growing the queue
    max_pending: int | None = None
    overload_factor: float | None = None
    # blocks a staged KVHandoff stays adoptable before the lease
    # watchdog may reclaim its pages and re-enqueue the victim
    handoff_lease_blocks: int = 64
    # cold-tier parking of preemption stashes (class default so
    # scheduler-only harnesses that skip __init__ resolve it): None =
    # disabled, 0 = stash victims directly to cold, N > 0 = park
    # stashes older than N decode blocks
    cold_park_after_blocks: int | None = None

    def __init__(self, model, params, *, batch_size: int = 4,
                 max_seq: int = 256, temperature: float = 0.0, seed: int = 0,
                 block_size: int = 8, eos_id: int | None = None,
                 paged: bool | None = None, page_size: int | None = None,
                 num_pages: int | None = None, pipeline: bool = True,
                 prefix_cache: bool = True, mesh=None, preempt: bool = True,
                 preempt_policy="lru", audit: bool | None = None,
                 swap_retries: int = 3, swap_timeout_s: float | None = None,
                 deterministic: bool = True, prefill_async: bool = False,
                 prefill_chunk_tokens: int | None = None,
                 max_pending: int | None = None,
                 overload_factor: float | None = None,
                 handoff_lease_blocks: int = 64,
                 cold_park_after_blocks: int | None = None,
                 record_logits: bool = False):
        self.model = model
        self.record_logits = bool(record_logits)
        self.batch = batch_size
        self.max_seq = max_seq
        self.block_size = block_size
        self.temperature = temperature
        self.eos_id = eos_id
        self.seed = seed
        self._preempt_arg = bool(preempt)
        self.preempt_policy = preempt_policy
        self.audit_every_block = (audit if audit is not None
                                  else os.environ.get("REPRO_AUDIT") == "1")
        self._swap_retries = swap_retries
        self._swap_timeout_s = swap_timeout_s
        self.max_pending = max_pending
        self.overload_factor = overload_factor
        self.handoff_lease_blocks = handoff_lease_blocks
        # cold-tier parking of preemption stashes: None = disabled (the
        # pre-hierarchy behavior, zero drift); 0 = deep preemption —
        # victims stash DIRECTLY to the cold tier (the remote tier never
        # holds them); N > 0 = stashes older than N decode blocks are
        # demoted remote -> cold by the park sweep.  Either way a parked
        # victim promotes back THROUGH the remote tier on resume and
        # decodes bit-identically (tier moves never touch the bytes).
        self.cold_park_after_blocks = cold_park_after_blocks
        if paged is None:
            paged = getattr(model, "supports_paged_kv", lambda: False)()
        self.paged = bool(paged)
        if prefill_async and not self.paged:
            raise ValueError("prefill_async requires the paged KV cache "
                             "(the engines hand off pool pages)")
        if prefill_async and record_logits:
            raise ValueError("record_logits does not cover handoff "
                             "adoption (prefill_async)")
        self._prefill_async = bool(prefill_async)
        self._prefill_chunk_tokens = prefill_chunk_tokens
        # the model's orchestrator (shared ledger: weight windows, expert
        # residency and KV pool report into one per-tier accounting);
        # models without one get a fresh plan from their config.
        self.mem: MemoryOrchestrator = (
            getattr(model, "mem", None) or MemoryOrchestrator.plan(model.cfg))
        self.deterministic = bool(deterministic)
        # validate BEFORE binding: a rejected mesh must not leave the
        # model's shared orchestrator/ledger in sharded mode
        spec_fn = None
        if mesh is not None:
            model.cfg.assert_mesh_compatible(mesh_axis_sizes(mesh))
            if self.deterministic:
                spec_fn = getattr(model, "serving_param_specs", None)
                if spec_fn is None:
                    raise ValueError(
                        f"{type(model).__name__} does not expose "
                        f"serving_param_specs; its family is not wired for "
                        f"the all-gather-TP serving placement, and serving "
                        f"it over a mesh would emit silently diverging "
                        f"tokens (partial-sum rounding)")
            else:
                # opt-in Megatron row-parallel serving: wo stays
                # contraction-sharded, partial sums all-reduce
                spec_fn = model.param_specs
        self.mesh = mesh
        self.mem.bind_mesh(mesh)
        try:
            self._init_live_state(model, params, spec_fn, batch_size,
                                  max_seq, seed, page_size, num_pages,
                                  pipeline, prefix_cache, mesh)
        except BaseException:
            # ANY post-bind construction failure (param tree mismatch,
            # placement error, cache init) must not leave the model's
            # shared orchestrator/ledger in sharded mode
            self.mem.bind_mesh(None)
            raise

    def _init_sched_state(self, batch_size: int) -> None:
        """Pure-host scheduler state: queues, reservations, outcome and
        lifecycle bookkeeping, stats.  Split out of the device-touching
        construction so the scheduler-only test harnesses (which skip
        ``__init__`` and fake the device steps) initialize EXACTLY the
        state the real scheduler methods touch — one source of truth
        for what the scheduler needs."""
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self._backlog: collections.deque[Request] = collections.deque()
        self._uid = 0
        self._preempted: list[_Preempted] = []   # resume-FIFO
        self._reserved: dict[int, int] = {}    # slot -> worst-case pages
        self._planned = [0] * batch_size       # in-flight decode tokens
        self._pool_fault = False       # mid-decode exhaustion latched
        self._fault_release_block: int | None = None
        self._fault_slot = -1          # phantom slot holding stolen pages
        self._sched_counter = 0
        self._last_sched = [0] * batch_size      # for the LRU policy
        self._peak_pages = -1
        self.tiers_peak: dict = {}
        # request-lifecycle robustness state: slots whose harvest hit
        # non-finite logits (slot -> poisoned request), orphaned prefill
        # pseudo-slots and un-adopted handoffs left behind by an engine
        # crash (reclaimed by the lease watchdog), and the admission-
        # control view of not-yet-started demand
        self._poisoned: dict[int, Request] = {}
        self._orphan_prefills: list[tuple[int, Request]] = []
        self._orphan_handoffs: list = []         # KVHandoff
        self._pending_count = 0
        self._pending_pages = 0
        self._pending_lock = threading.Lock()
        # decode-stall accounting: prompt tokens dispatched synchronously
        # ahead of pending decode work since the last decode dispatch —
        # folded into decode_stall_blocks_* at the next dispatch
        self._stall_tokens = 0
        self._ttft_samples: list[int] = []
        self._e2e_samples: list[int] = []
        # what held the backlog head at the last admission check
        # ("pages", "slots" or None) and since when (host clock)
        self._blocked: str | None = None
        self._blocked_since = 0.0
        self.stats = {"steps": 0, "tokens": 0, "batches": 0, "blocks": 0,
                      "dispatches": 0, "admitted": 0, "host_syncs": 0,
                      "kv_pages_in_use": 0, "kv_pages_hwm": 0,
                      "compiles": 0, "table_rebuilds": 0,
                      "table_delta_entries": 0, "prefix_hits": 0,
                      "prefix_shared_pages": 0,
                      "preemptions": 0, "resumes": 0, "sheds": 0,
                      "cold_parks": 0, "cold_promotes": 0,
                      "preempted_pages": 0, "pool_faults": 0,
                      "prefix_drops": 0, "swap_retries": 0,
                      "slow_transfers": 0, "audits": 0,
                      "model_shards": getattr(getattr(self, "mem", None),
                                              "model_shards", 1),
                      "prefill_chunks": 0, "handoffs": 0,
                      "decode_stall_blocks_max": 0,
                      "decode_stall_blocks_total": 0,
                      "ttft_p50_blocks": 0.0, "ttft_p99_blocks": 0.0,
                      "completed": 0, "rejected": 0, "expired": 0,
                      "poison_sheds": 0, "engine_crashes": 0,
                      "lease_reclaims": 0, "crash_requeues": 0,
                      "e2e_p50_blocks": 0.0, "e2e_p99_blocks": 0.0,
                      # table columns the paged kernel fetches per
                      # layer, and those holding an attended position
                      "kv_pages_visited": 0, "kv_pages_live": 0,
                      # host seconds the backlog head waited, by cause
                      "queue_blocked_pages_s": 0.0,
                      "queue_blocked_slots_s": 0.0}

    def _init_live_state(self, model, params, spec_fn, batch_size, max_seq,
                         seed, page_size, num_pages, pipeline, prefix_cache,
                         mesh) -> None:
        """Everything after the mesh is bound: placement, jit entry
        points, caches, slot state (split out so __init__ can unbind the
        mesh if any of it fails)."""
        self._init_sched_state(batch_size)
        if spec_fn is not None:
            # serving placement: all-gather TP (output projections
            # replicated) so sharded tokens are bit-identical — see
            # DenseLM.serving_param_specs.  deterministic=False keeps
            # the training-layout row-parallel shards instead.
            params = self.mem.place_params(params, spec_fn())
        self.params = params
        self.pipeline = bool(pipeline)
        self.max_inflight = 2 if self.pipeline else 1
        self.prefix_cache = bool(prefix_cache)
        self._decode_loop = make_decode_loop(
            model, block_size=self.block_size, temperature=self.temperature,
            eos_id=self.eos_id, detect_nonfinite=True,
            emit_logits=self.record_logits)
        self._admit_step = self.mem.donating_jit(self._make_admit_step(),
                                                 donate_argnums=(2, 3))
        self._admit_step_prefix = None
        # live slot state — donated through every dispatch
        if self.paged:
            cfg = model.cfg
            self.page_size = page_size or cfg.page_size
            per_seq = -(-max_seq // self.page_size)
            self.num_pages = num_pages or batch_size * per_seq + 1
            self.kv = self.mem.block_pool(self.num_pages, self.page_size)
            self.manager = self.kv.manager
            quantized = bool(getattr(cfg, "kv_quantized", False))
            pool_dt = (cfg.kv_pool_dtype() if quantized else cfg.dtype)
            self.kv.bind_kv_shape(cfg.padded_kv_heads, cfg.head_dim,
                                  jnp.dtype(pool_dt).itemsize,
                                  cfg.num_layers,
                                  scale_itemsize=2 if quantized else 0)
            self.cache = self.mem.place_kv_pool(
                model.init_paged_cache(self.num_pages, self.page_size),
                specs=(model.paged_cache_specs() if mesh is not None
                       else None))
            self._admit_step_prefix = self.mem.donating_jit(
                self._make_admit_step_prefix(), donate_argnums=(2, 3))
            # persistent device-resident page table: starts at the
            # canonical width-1 null table; the host mirror below tracks
            # its exact device contents so block deltas can be computed
            # without ever re-reading (or re-sending) the whole table
            self._table_w = 1
            self._narrow_blocks = 0
            self._mirror = np.zeros((batch_size, 1), np.int32)
            init_pages = self._dev(jnp.asarray(self._mirror))
        else:
            self.kv = None
            self.manager = None
            # dense slab: resident at full size regardless of occupancy
            # (capacity == residency), in the kv_pool policy's tier;
            # per-shard bytes under a mesh (heads axis "model"-sharded)
            self.cache = self.mem.place_kv_pool(
                model.init_cache(batch_size, max_seq),
                specs=(model.cache_specs() if mesh is not None else None))
            self.mem.ledger.record(
                self.mem.policies["kv_pool"].tier, "kv_pool",
                self.mem.placed_bytes(self.cache))
            init_pages = None
        # per-request PRNG: every request uid gets fold_in(base, uid);
        # the token at sequence position q is sampled from
        # fold_in(request_key, q), making sampling a pure function of
        # (seed, uid, position) — invariant under preemption, resume,
        # snapshot/restore and scheduling order
        self._base_key = jax.random.PRNGKey(seed)
        self.state = DecodeState.init(
            batch_size, jax.random.PRNGKey(seed), pages=init_pages,
            slot_keys=jnp.zeros((batch_size, 2), jnp.uint32))
        if mesh is not None:
            # decode state is host-mirrored bookkeeping: replicate it
            self.state = jax.device_put(self.state, replicated(mesh))
        self.slots: list[Request | None] = [None] * batch_size
        self._slot_pos = [0] * batch_size      # host mirror of state.pos
        # preemption / fault-recovery state (paged only)
        self.preempt_enabled = self._preempt_arg and self.paged
        self.transfer_monitor = StragglerMonitor(factor=3.0)
        self.swapper = (PageSwapper(ledger=self.mem.ledger,
                                    retries=self._swap_retries,
                                    timeout_s=self._swap_timeout_s,
                                    monitor=self.transfer_monitor)
                        if self.paged else None)
        # disaggregated prefill/decode: the async prefill engine drains
        # the backlog in chunks and hands finished prompts to decode as
        # KV page handoffs (see repro.runtime.prefill)
        self.prefill = None
        if self._prefill_async:
            from repro.runtime.prefill import PrefillEngine
            self.prefill = PrefillEngine(
                self, chunk_tokens=self._prefill_chunk_tokens)

            def adopt_step(state, nxt, slot, plen, remaining, key):
                """Handoff adoption splice, fused into one dispatch
                (the un-jitted ``.at[].set`` chain costs ~5 tiny device
                round trips per adoption — measurable at smoke scale)."""
                return dataclasses.replace(
                    state,
                    tokens=state.tokens.at[slot, 0].set(nxt[0, 0]),
                    pos=state.pos.at[slot].set(plen),
                    active=state.active.at[slot].set(True),
                    remaining=state.remaining.at[slot].set(remaining),
                    slot_keys=state.slot_keys.at[slot].set(key))

            self._adopt_step = self.mem.donating_jit(adopt_step,
                                                     donate_argnums=(0,))

    # ----- mesh plumbing -----------------------------------------------------
    def _mesh_ctx(self):
        """Ambient-mesh context for every trace/dispatch, with the
        all-gather-TP constraints armed (they belong to the serving
        placement ONLY — other mesh users like the dry-run keep the
        Megatron row-parallel lowering).  No-op context single-device."""
        if self.mesh is None:
            return contextlib.nullcontext()
        stack = contextlib.ExitStack()
        stack.enter_context(activate_mesh(self.mesh))
        if self.deterministic:
            stack.enter_context(gather_tp_mode())
        return stack

    def _dev(self, x: jax.Array) -> jax.Array:
        """Pin a host-built array (page tables, deltas) to its
        steady-state placement: replicated on the mesh, so dispatches see
        one consistent input sharding instead of compiling an extra
        executable for the uncommitted first transfer."""
        if self.mesh is None:
            return x
        return jax.device_put(x, replicated(self.mesh))

    # ----- request intake ----------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32, *,
               deadline_blocks: int | None = None) -> Request:
        """Enqueue a request.  ``deadline_blocks`` (optional) is an SLA
        TTL in decode-block units: once that many blocks elapse without
        completion the request is cancelled at whatever stage it is in
        and finishes with ``outcome == "expired"``.

        Under overload admission control (``max_pending`` /
        ``overload_factor``) a request the server cannot credibly serve
        is REJECTED here — returned immediately with ``done`` set,
        ``outcome == "rejected"`` and a structured ``error`` — instead
        of joining an unbounded queue."""
        prompt = np.asarray(prompt, np.int32)
        # validate HERE so the caller sees the error; a raise mid-admission
        # would drop an already-dequeued request with done never set
        if len(prompt) + max(max_new_tokens - 1, 0) > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" exceeds max_seq={self.max_seq}")
        worst = 0
        if self.paged:
            worst = self._worst_pages(len(prompt), max_new_tokens)
            if worst > self.manager.capacity:
                raise ValueError(
                    f"request needs up to {worst} KV pages but the pool "
                    f"only has {self.manager.capacity}")
        self._uid += 1
        req = Request(self._uid, prompt, max_new_tokens=max_new_tokens,
                      t_submit=time.perf_counter())
        req.submitted_block = self.stats["blocks"]
        req.deadline_blocks = deadline_blocks
        overload = self._admission_gate(req, worst)
        if overload is not None:
            req.error = {"reason": "admission_rejected", "detail": overload,
                         "uid": req.uid, "tokens_emitted": 0}
            self._finalize(req, "rejected")
            return req
        self.queue.put(req)
        return req

    # ----- request lifecycle: outcomes, deadlines, overload control -----------
    # terminal outcome -> stats counter it increments
    _OUTCOME_KEYS = {"completed": "completed", "shed": "sheds",
                     "rejected": "rejected", "expired": "expired"}

    def _finalize(self, req: Request, outcome: str,
                  finished: list[Request] | None = None) -> None:
        """The ONE terminal transition of a request: stamp its outcome,
        release its admission-control accounting, count it, sample e2e
        latency (completions only) and set ``done``.  Idempotent — every
        cancellation path funnels here, so racing paths (e.g. a lease
        reclaim against a deadline sweep) can never double-count."""
        if req.outcome is not None:
            return
        req.outcome = outcome
        self._pending_remove(req)
        self.stats[self._OUTCOME_KEYS[outcome]] += 1
        if outcome == "completed" and req.submitted_block is not None:
            self._e2e_samples.append(self.stats["blocks"]
                                     - req.submitted_block)
        req.done.set()
        if finished is not None:
            finished.append(req)

    def _admission_gate(self, req: Request, worst: int) -> str | None:
        """Overload admission control, one lock hold: accept (count the
        request into the pending demand view and return None) or return
        the structured-rejection detail.  The page term projects the
        ledger-backed worst case — live reservations plus every
        not-yet-started request's worst-case page need — against
        ``overload_factor x`` pool capacity: demand beyond that cannot
        make its deadline anyway, so rejecting it FAST keeps the
        admitted requests' tail latency bounded."""
        with self._pending_lock:
            if (self.max_pending is not None
                    and self._pending_count >= self.max_pending):
                return (f"pending requests at max_pending="
                        f"{self.max_pending}")
            if (self.overload_factor is not None and self.paged
                    and self.manager is not None):
                projected = (sum(self._reserved.values())
                             + self._pending_pages + worst)
                budget = self.overload_factor * self.manager.capacity
                if projected > budget:
                    return (f"projected worst-case demand {projected} pages"
                            f" > {budget:.0f} "
                            f"(overload_factor={self.overload_factor} x "
                            f"capacity {self.manager.capacity})")
            req._pending_counted = True
            self._pending_count += 1
            self._pending_pages += worst
            return None

    def _pending_add(self, req: Request) -> None:
        """(Re-)count a not-yet-started request into the admission-
        control demand view (crash requeue, restore, admission
        rollback).  Flag-guarded: never double-counts."""
        with self._pending_lock:
            if not req._pending_counted:
                req._pending_counted = True
                self._pending_count += 1
                if self.paged and self.manager is not None:
                    self._pending_pages += self._worst_pages(
                        len(req.prompt), req.max_new_tokens)

    def _pending_remove(self, req: Request) -> None:
        with self._pending_lock:
            if req._pending_counted:
                req._pending_counted = False
                self._pending_count -= 1
                if self.paged and self.manager is not None:
                    self._pending_pages -= self._worst_pages(
                        len(req.prompt), req.max_new_tokens)

    def _deadline_passed(self, req: Request) -> bool:
        return (req.deadline_blocks is not None
                and req.submitted_block is not None
                and self.stats["blocks"]
                >= req.submitted_block + req.deadline_blocks)

    def _expire_req(self, req: Request, finished: list[Request],
                    stage: str) -> None:
        req.error = {"reason": "deadline_expired",
                     "detail": f"deadline of {req.deadline_blocks} blocks "
                               f"passed while {stage}",
                     "uid": req.uid, "tokens_emitted": len(req.output)}
        self._finalize(req, "expired", finished)

    def _expiry_stall(self) -> bool:
        """A LIVE slot past its deadline stalls dispatch so the pipeline
        drains before eviction — evicting a slot with a later block in
        flight and re-admitting into it would mis-attribute that block's
        harvested tokens to the new occupant."""
        return any(r is not None and self._deadline_passed(r)
                   for r in self.slots)

    def _expire_sweep(self, finished: list[Request], drained: bool) -> None:
        """Cancel every expired request at whatever lifecycle stage it
        is in — backlog, swapped-out victim, mid-prefill, staged
        handoff, and (only with the pipeline drained) live decode slot —
        reclaiming its pages so ``audit()`` stays clean."""
        if self._backlog and any(self._deadline_passed(r)
                                 for r in self._backlog):
            keep: collections.deque = collections.deque()
            for req in self._backlog:
                if self._deadline_passed(req):
                    self._expire_req(req, finished, "backlogged")
                else:
                    keep.append(req)
            self._backlog = keep
        for ps in list(self._preempted):
            if self._deadline_passed(ps.req):
                self._preempted.remove(ps)
                if self.swapper is not None and ps.handle is not None:
                    self.swapper.release(ps.handle)
                self._expire_req(ps.req, finished, "preempted")
        eng = self.prefill
        if eng is not None:
            for inf in list(eng.inflight):
                if self._deadline_passed(inf.req):
                    eng.inflight.remove(inf)
                    self.manager.free_slot(inf.slot)
                    self._reserved.pop(inf.slot, None)
                    self._expire_req(inf.req, finished, "mid-prefill")
                    self.kv.record()
            for h in list(eng.ready):
                if self._deadline_passed(h.req):
                    eng.ready.remove(h)
                    self.manager.release_handoff(h.token)
                    self._reserved.pop(h.pslot, None)
                    eng.staging.release(h.handle)
                    self._expire_req(h.req, finished, "staged for handoff")
                    self.kv.record()
        if drained:
            for i, req in enumerate(self.slots):
                if req is not None and self._deadline_passed(req):
                    self._evict_slot(i)
                    self._expire_req(req, finished, "decoding")
                    if self.kv is not None:
                        self.kv.record()

    def _requeue(self, req: Request, finished: list[Request]) -> None:
        """Put an engine-crash victim back at the FRONT of the backlog
        (it is older than everything queued behind it) — unless its
        deadline already passed, in which case the retry would be dead
        on arrival.  The retried tokens are bit-identical to the lost
        attempt's at any temperature: prefill and sampling are pure
        functions of (seed, uid, position)."""
        if self._deadline_passed(req):
            self._expire_req(req, finished, "awaiting crash retry")
            return
        self._backlog.appendleft(req)
        self._pending_add(req)
        self.stats["crash_requeues"] += 1

    def _reclaim_orphan_handoff(self, h, finished: list[Request]) -> None:
        """Release an orphaned/expired handoff's pool pages through the
        manager's handoff registry, drop its staged remote-tier bytes,
        and retry the victim."""
        self.manager.release_handoff(h.token)
        self._reserved.pop(h.pslot, None)
        if h.handle is not None and self.prefill is not None:
            self.prefill.staging.release(h.handle)
        self.stats["lease_reclaims"] += 1
        self._requeue(h.req, finished)
        if self.kv is not None:
            self.kv.record()

    def _lease_watchdog(self, finished: list[Request],
                        force: bool = False) -> None:
        """Reclaim engine-crash leftovers.  A crashed prefill's partial
        pages are garbage — freed and the victim retried immediately.
        An un-adopted handoff holds COMPLETE, adoptable state, so its
        pages stay pinned until its lease expires (another decode engine
        might still adopt it); then the registry entry is released and
        the victim retried.  ``force=True`` (snapshot) cuts every lease
        short — a restart is a new lease epoch."""
        if self._orphan_prefills:
            for pslot, req in self._orphan_prefills:
                self.manager.free_slot(pslot)
                self._reserved.pop(pslot, None)
                self._requeue(req, finished)
            self._orphan_prefills.clear()
            if self.kv is not None:
                self.kv.record()
        for h in list(self._orphan_handoffs):
            if (force or self.stats["blocks"] >= h.lease_expiry_block
                    or self._deadline_passed(h.req)):
                self._orphan_handoffs.remove(h)
                self._reclaim_orphan_handoff(h, finished)
        eng = self.prefill
        if eng is not None and eng.ready:
            # leases bind NON-crashed handoffs too: one staged longer
            # than its lease (decode wedged, no free slot) is reclaimed
            # and retried rather than pinning pool pages indefinitely
            for h in list(eng.ready):
                if self.stats["blocks"] >= h.lease_expiry_block:
                    eng.ready.remove(h)
                    self._reclaim_orphan_handoff(h, finished)

    # ----- admission ---------------------------------------------------------
    def _admit_plen(self, prompt_len: int, max_new_tokens: int) -> int:
        """Bucketed admission prompt length (see _admit)."""
        limit = self.max_seq - max(max_new_tokens - 1, 0)
        bucket = _bucket(prompt_len)
        return bucket if bucket <= limit else prompt_len

    def _make_admit_step(self) -> Callable:
        return (self._make_admit_step_paged() if self.paged
                else self._make_admit_step_dense())

    def _make_admit_step_dense(self) -> Callable:
        model, max_seq = self.model, self.max_seq
        vocab, temperature = self.model.cfg.vocab, self.temperature
        eos_id = self.eos_id

        def admit_step(params, ptoks, cache, state, slot, max_new, req_key):
            """Prefill ONE request and splice it into the live batch state.

            ptoks: (1, P) left-padded prompt; slot/max_new: traced
            scalars; req_key: (2,) uint32 per-request key.  The first
            token lands at sequence position ``plen``, so it is sampled
            from ``fold_in(req_key, plen)`` — the same rule the decode
            loop applies per slot.  Donates (cache, state) — the splice
            is in place.
            """
            fresh = model.init_cache(1, max_seq)
            logits, fresh = model.prefill(params, ptoks, fresh)
            k = jax.random.fold_in(req_key, ptoks.shape[1])
            nxt = sample_tokens(logits, vocab, temperature, k)   # (1, 1)

            def splice(big, small):
                """Write the single-request leaf into the batch leaf at
                ``slot``.  The batch axis is found per leaf (the unique
                axis where the shapes differ), so non-transformer caches
                — e.g. recurrent state with batch leading — splice too."""
                if big.shape == small.shape:  # batch-1 server: whole swap
                    return small.astype(big.dtype)
                diff = [i for i, (bs, ss) in enumerate(zip(big.shape,
                                                           small.shape))
                        if bs != ss]
                if len(diff) != 1:
                    raise ValueError(
                        f"cannot infer the batch axis of cache leaf "
                        f"{big.shape} from single-request leaf "
                        f"{small.shape}")
                ax = diff[0]
                starts = (0,) * ax + (slot,) + (0,) * (big.ndim - ax - 1)
                return jax.lax.dynamic_update_slice(
                    big, small.astype(big.dtype), starts)

            cache = jax.tree.map(splice, cache, fresh)
            plen = ptoks.shape[1]
            state = self._spliced_state(state, nxt, plen, slot, max_new,
                                        req_key)
            return nxt, logits, cache, state
        return admit_step

    def _make_admit_step_paged(self) -> Callable:
        model = self.model
        vocab, temperature = self.model.cfg.vocab, self.temperature

        def admit_step(params, ptoks, cache, state, slot, max_new, req_key,
                       ptable):
            """Prefill ONE request straight into its freshly allocated
            pages — no dense staging cache, no splice.  ptable: (1, n)
            page ids covering the bucketed prompt.  Donates (cache,
            state): the page writes and slot activation are in place."""
            logits, cache = model.prefill_paged(params, ptoks, cache, ptable)
            k = jax.random.fold_in(req_key, ptoks.shape[1])
            nxt = sample_tokens(logits, vocab, temperature, k)   # (1, 1)
            plen = ptoks.shape[1]
            state = self._spliced_state(state, nxt, plen, slot, max_new,
                                        req_key)
            return nxt, logits, cache, state
        return admit_step

    def _make_admit_step_prefix(self) -> Callable:
        model = self.model
        vocab, temperature = self.model.cfg.vocab, self.temperature

        def admit_step(params, ptoks, cache, state, slot, max_new, req_key,
                       prefix_pages, new_pages):
            """Prefix-cached admission: prefill ONLY the prompt suffix.

            ptoks: (1, S_new) suffix tokens (position n_pre*page
            onwards); prefix_pages: (1, n_pre) shared pages read, never
            written; new_pages: (1, n_new) pages receiving the suffix
            KV.  Sampling folds the request key with the SAME total
            prompt length as the unshared path, so shared and unshared
            admission stay PRNG-identical."""
            logits, cache = model.prefill_paged_prefix(
                params, ptoks, cache, prefix_pages, new_pages)
            page = cache["k_pages"].shape[3]
            plen = prefix_pages.shape[1] * page + ptoks.shape[1]
            k = jax.random.fold_in(req_key, plen)
            nxt = sample_tokens(logits, vocab, temperature, k)   # (1, 1)
            state = self._spliced_state(state, nxt, plen, slot, max_new,
                                        req_key)
            return nxt, logits, cache, state
        return admit_step

    def _spliced_state(self, state, nxt, plen, slot, max_new, req_key):
        """Activate ``slot`` in the decode state (shared by both admit
        paths) and install the request's per-slot PRNG key.  The page
        table is NOT touched here — the host refreshes it at every block
        boundary."""
        active = max_new > 1
        if self.eos_id is not None:   # EOS at admission: never activate
            active = active & (nxt[0, 0] != self.eos_id)
        upd1 = lambda buf, val: jax.lax.dynamic_update_slice(
            buf, jnp.asarray(val, buf.dtype)[None], (slot,))
        return DecodeState(
            tokens=jax.lax.dynamic_update_slice(state.tokens, nxt,
                                                (slot, 0)),
            pos=upd1(state.pos, plen),
            active=upd1(state.active, active),
            remaining=upd1(state.remaining, max_new - 1),
            key=state.key, pages=state.pages,
            slot_keys=jax.lax.dynamic_update_slice(
                state.slot_keys, req_key.astype(jnp.uint32)[None],
                (slot, 0)))

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    def _worst_pages(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case page need of a request over its whole lifetime."""
        plen = self._admit_plen(prompt_len, max_new_tokens)
        return self.manager.pages_for(
            min(plen + max(max_new_tokens - 1, 0), self.max_seq))

    def _admission_pages_ready(self, req: Request) -> bool:
        """Page-accounting gate: every admitted request RESERVES its
        worst-case page count (allocation itself stays on-demand, so the
        live footprint still tracks actual tokens) — mid-decode pool
        exhaustion is then impossible (absent an injected fault, which
        emergency preemption recovers), and queued requests wait for
        reclamation or trigger preemption."""
        reserved = sum(self._reserved.values())
        worst = self._worst_pages(len(req.prompt), req.max_new_tokens)
        return worst <= self.manager.capacity - reserved

    def _req_key(self, uid: int) -> jax.Array:
        """The per-request PRNG key: ``fold_in(PRNGKey(seed), uid)`` —
        a pure function of construction seed and admission order, so
        identically configured servers sample identically."""
        return jax.random.fold_in(self._base_key, uid)

    def _under_pressure(self) -> bool:
        """Pool-pressure predicate for graceful degradation: sharing new
        prefix pages is skipped while victims sit swapped out (their
        resume must not contend with refcount-pinned pages) or while
        worst-case reservations crowd the pool."""
        if not self.paged:
            return False
        if self._preempted or self._pool_fault:
            return True
        return sum(self._reserved.values()) > 0.9 * self.manager.capacity

    # ----- prefix caching ----------------------------------------------------
    def _shareable_pages(self, plen: int) -> int:
        """Prompt pages eligible for sharing: whole pages strictly before
        the last prompt token.  The final page — partial or not — stays
        private so admission always has at least one suffix token to
        prefill (the one whose logits seed sampling), and decode's first
        write (position >= plen) can never touch a shared page."""
        return (plen - 1) // self.page_size

    def _shared_prefix_pages(self, toks: np.ndarray, plen: int) -> list[int]:
        """Longest run of already-pooled pages matching this padded
        prompt's leading whole pages.  Keys are the exact padded token
        bytes up to each page boundary — positions matter (left-padding
        included), so a hit guarantees bit-identical KV."""
        page, out = self.page_size, []
        for i in range(self._shareable_pages(plen)):
            pid = self.manager.lookup_prefix(toks[0, :(i + 1) * page]
                                             .tobytes())
            if pid is None:
                break
            out.append(pid)
        return out

    def _register_prefix(self, toks: np.ndarray, plen: int,
                         slot: int) -> None:
        """Publish this admission's freshly written whole prompt pages
        for future sharers (already-shared leading pages re-register as
        no-ops; the index keeps the first writer)."""
        page = self.page_size
        table = self.manager.slot_pages(slot)
        for i in range(self._shareable_pages(plen)):
            self.manager.register_prefix(toks[0, :(i + 1) * page].tobytes(),
                                         table[i])

    def _note_peak(self) -> None:
        """Capture a mid-flight per-tier ledger snapshot whenever pool
        occupancy reaches a new (or equal) peak, so the bench's residency
        block reflects peak load rather than the drained end state."""
        if self.manager.pages_in_use >= self._peak_pages:
            self._peak_pages = self.manager.pages_in_use
            self.tiers_peak = self.mem.ledger.snapshot()

    def _admit(self, req: Request, slot: int) -> bool:
        """Prefill ``req`` into ``slot`` of the live batch; True if the
        request finished at admission (budget of 1 / immediate EOS).

        Left-pad tokens (id 0) inside the bucket are attended like the
        seed server attended its batch-wide left-padding — deterministic,
        but outputs depend on the bucket quantum (see EXPERIMENTS.md).
        """
        # the bucketed start position must leave room for every decode
        # write (pos < max_seq, KV scatter past the cache end is silently
        # dropped by jit) — fall back to the exact prompt length (one
        # extra compile) when the bucket would overflow
        plen = self._admit_plen(len(req.prompt), req.max_new_tokens)
        toks = np.zeros((1, plen), np.int32)
        toks[0, plen - len(req.prompt):] = req.prompt        # left-pad
        req_key = self._req_key(req.uid)
        # admission never reads or writes the device page table, so hold
        # it aside and admit with pages=None: admit executables are then
        # keyed only on the bucketed prompt shape, never on whatever
        # width the live table happens to have (the width x plen compile
        # cross-product would otherwise defeat the bucketing).
        # try/finally: a MemoryError from ensure() (injected pool
        # exhaustion) must not leave the live state without its table.
        saved_pages = self.state.pages
        if saved_pages is not None:
            self.state = dataclasses.replace(self.state, pages=None)
        try:
            if self.paged:
                self._reserved[slot] = self._worst_pages(
                    len(req.prompt), req.max_new_tokens)
                share = self.prefix_cache
                if share and self._under_pressure():
                    # degradation policy: under pool pressure new
                    # admissions neither reuse nor publish shared pages
                    # (sharing is semantically invisible, so tokens are
                    # unchanged — only residency is)
                    share = False
                    self.stats["prefix_drops"] += 1
                shared = (self._shared_prefix_pages(toks, plen)
                          if share else [])
                if shared:
                    self.manager.adopt(slot, shared)
                new_ids = self.manager.ensure(slot, plen)
                if shared:
                    suffix = toks[:, len(shared) * self.page_size:]
                    self._note_prefill_dispatch(suffix.shape[1])
                    with self._mesh_ctx():
                        (nxt, logits, self.cache,
                         self.state) = self._admit_step_prefix(
                            self.params, jnp.asarray(suffix), self.cache,
                            self.state, jnp.asarray(slot, jnp.int32),
                            jnp.asarray(req.max_new_tokens, jnp.int32),
                            req_key,
                            jnp.asarray([shared], jnp.int32),
                            jnp.asarray([new_ids], jnp.int32))
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_shared_pages"] += len(shared)
                else:
                    ptable = jnp.asarray([new_ids], jnp.int32)
                    self._note_prefill_dispatch(plen)
                    with self._mesh_ctx():
                        nxt, logits, self.cache, self.state = self._admit_step(
                            self.params, jnp.asarray(toks), self.cache,
                            self.state, jnp.asarray(slot, jnp.int32),
                            jnp.asarray(req.max_new_tokens, jnp.int32),
                            req_key, ptable)
                self.manager.note_tokens(slot, plen)
                if share:
                    self._register_prefix(toks, plen, slot)
                self.kv.record()
                self._note_peak()
            else:
                self._note_prefill_dispatch(plen)
                with self._mesh_ctx():
                    nxt, logits, self.cache, self.state = self._admit_step(
                        self.params, jnp.asarray(toks), self.cache,
                        self.state, jnp.asarray(slot, jnp.int32),
                        jnp.asarray(req.max_new_tokens, jnp.int32), req_key)
        finally:
            if saved_pages is not None and self.state.pages is None:
                self.state = dataclasses.replace(self.state,
                                                 pages=saved_pages)
        self.cache = self.mem.rest_kv_pool(self.cache)
        self._slot_pos[slot] = plen
        self._sched_counter += 1
        self._last_sched[slot] = self._sched_counter
        req.admitted_at_block = self.stats["blocks"]
        with TraceAnnotation("serve.sync"):
            first = int(jax.device_get(nxt)[0, 0])
        req.output.append(first)
        if self.record_logits:
            req.logits.append(np.asarray(jax.device_get(logits))[0, -1])
        self._record_first_token(req)
        self.stats["tokens"] += 1
        self.stats["admitted"] += 1
        if req.max_new_tokens <= 1 or (self.eos_id is not None
                                       and first == self.eos_id):
            if self.paged:
                self.manager.free_slot(slot)   # reclaim at once
                self._reserved.pop(slot, None)
                self.kv.record()    # ledger must track the reclaim
            self._finalize(req, "completed")
            return True
        self.slots[slot] = req
        return False

    def _admit_from_queue(self, finished: list[Request],
                          allow_preempt: bool = False) -> None:
        """Fill free slots (non-blocking, mid-stream): swapped-out
        victims resume FIRST (resume-FIFO — they are older than every
        queued request, so preemption can never starve them), then the
        backlog in arrival order.  With a paged pool, admission is
        page-gated: the head request waits (FIFO order preserved) until
        reclamation frees enough — or, with ``allow_preempt`` (the
        pipeline is drained), triggers page-granular preemption.

        Lifecycle upkeep runs first: crash leftovers are reclaimed and
        expired requests cancelled (live slots only when the pipeline is
        drained — ``allow_preempt`` doubles as that signal)."""
        self._drain_queue()
        self._lease_watchdog(finished)
        self._expire_sweep(finished, drained=allow_preempt)
        while self._preempted and self._free_slots():
            ps = self._preempted[0]
            if not self._resume_ready(ps):
                break
            self._preempted.pop(0)
            if not self._resume(ps, self._free_slots()[0], finished):
                self._preempted.insert(0, ps)   # physically blocked
                break
        if self.prefill is not None:
            self._async_admission(finished, allow_preempt)
        else:
            self._admit_backlog(finished, allow_preempt)
        self._note_blocked()

    def _admit_backlog(self, finished: list[Request],
                       allow_preempt: bool) -> None:
        """Monolithic admission: prefill backlogged requests into free
        slots in arrival order, behind the page gate."""
        while True:
            free = self._free_slots()
            if not free:
                return
            if not self._backlog:
                try:
                    self._backlog.append(self.queue.get_nowait())
                except queue.Empty:
                    return
            req = self._backlog[0]
            if self.paged and not self._admission_pages_ready(req):
                if not (allow_preempt and self._try_preempt_for(req,
                                                                finished)):
                    return            # blocked on pages, not on slots
                free = self._free_slots()
                if not free or not self._admission_pages_ready(req):
                    return
            self._backlog.popleft()
            self._pending_remove(req)
            t = time.perf_counter()
            try:
                with TraceAnnotation("serve.admit", uid=req.uid):
                    done_now = self._admit(req, free[0])
            except MemoryError:
                # physically out of pages (injected exhaustion window):
                # roll back the reservation and keep FIFO order
                self.manager.free_slot(free[0])
                self._reserved.pop(free[0], None)
                self._backlog.appendleft(req)
                self._pending_add(req)
                return
            if req.t_admit is None:
                req.t_admit = t
            if done_now:
                finished.append(req)      # done at admission: slot stays free

    def _note_blocked(self) -> None:
        """Close the interval since the previous admission check, adding
        its host seconds to the cause found then, and open the next: the
        backlog head waits on pages (a slot — the prefill engine's, under
        ``prefill_async`` — is free but the head's worst case does not
        fit the unreserved pool) or on slots (none is free).  An empty
        backlog waits on nothing."""
        now = time.perf_counter()
        if self._blocked is not None:
            self.stats[f"queue_blocked_{self._blocked}_s"] += (
                now - self._blocked_since)
        cause = None
        if self._backlog:
            if self.prefill is not None:
                free = len(self.prefill.inflight) < self.prefill.max_inflight
            else:
                free = bool(self._free_slots())
            cause = "pages" if free else "slots"
        self._blocked, self._blocked_since = cause, now

    # ----- disaggregated admission (async prefill engine) ---------------------
    def _async_admission(self, finished: list[Request],
                         allow_preempt: bool) -> None:
        """Admission through the prefill engine: starts are strictly
        FIFO behind the page gate, ONE prefill chunk advances per
        scheduling round while decode work is pending (so a long prompt
        never stalls decode for more than a chunk), and ready handoffs
        are adopted into free slots.  With decode idle the loop pumps
        freely — chunking costs nothing when there is nothing to
        stall."""
        eng = self.prefill
        while True:
            self._drain_queue()
            started = False
            while (self._backlog and len(eng.inflight) < eng.max_inflight
                   and self._admission_pages_ready(self._backlog[0])):
                req = self._backlog.popleft()
                self._pending_remove(req)
                if req.t_admit is None:
                    req.t_admit = time.perf_counter()
                with TraceAnnotation("serve.admit", uid=req.uid):
                    eng.start(req)
                started = True
            if (self._backlog and not started and allow_preempt
                    and not self._admission_pages_ready(self._backlog[0])
                    and self._try_preempt_for(self._backlog[0], finished)):
                continue
            progressed = eng.pump_once(finished)
            if not self._can_dispatch() and (progressed or started):
                # decode idle: finish the whole burst before adopting —
                # the first adoption would make decode dispatchable and
                # serialize the remaining prefills one chunk per block,
                # ramping the batch one slot at a time.  Batching the
                # burst here is exactly monolithic admission's timing
                # (it too admits every queued request before decoding),
                # and chunking costs nothing while nothing can stall.
                continue
            adopted = False
            while eng.ready and self._free_slots():
                self._adopt_handoff(eng.ready.popleft(),
                                    self._free_slots()[0], finished)
                adopted = True
            if self._can_dispatch():
                return               # decode work pending: yield to it
            if not (progressed or adopted or started):
                return               # engine drained or blocked

    def _adopt_handoff(self, h, slot: int, finished: list[Request]) -> None:
        """Decode-side adoption of a completed prefill: pure ownership
        transfer — the handoff's pool-resident pages rebind to ``slot``
        (their table lands in the next block's bucketed delta), the
        staged remote-tier bytes are released, and the slot state is
        spliced exactly like a resume at ``pos = plen``.  No prefill
        compute, no KV copy, no blocking dispatch."""
        plan = memtiers.active_fault_plan()
        if plan is not None and plan.take_adopt_crash(self.stats["blocks"]):
            # injected decode-engine crash mid-adoption: the handoff's
            # pages stay staged under the registry and LEASED — another
            # engine might still adopt them — so reclamation waits for
            # the lease watchdog, which then retries the victim
            self._orphan_handoffs.append(h)
            self.stats["engine_crashes"] += 1
            return
        req = h.req
        self.manager.adopt_from_handoff(slot, h.token)
        # worst-case reservation transfers from the prefill pseudo-slot
        self._reserved[slot] = self._reserved.pop(
            h.pslot, self._worst_pages(len(req.prompt), req.max_new_tokens))
        self.prefill.staging.release(h.handle)
        req.admitted_at_block = self.stats["blocks"]
        req.output.append(h.first_token)
        self._record_first_token(req)
        self.stats["tokens"] += 1
        self.stats["admitted"] += 1
        if req.max_new_tokens <= 1 or (self.eos_id is not None
                                       and h.first_token == self.eos_id):
            self.manager.free_slot(slot)     # done at adoption
            self._reserved.pop(slot, None)
            self._finalize(req, "completed", finished)
            self.kv.record()
            return
        # adoption never touches the device page table — hold it aside
        # so the splice executable is keyed on the state shape alone
        # (same idiom as _admit), then run the fused one-dispatch splice
        saved_pages = self.state.pages
        if saved_pages is not None:
            self.state = dataclasses.replace(self.state, pages=None)
        try:
            with self._mesh_ctx():
                self.state = self._adopt_step(
                    self.state, h.nxt, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(h.plen, jnp.int32),
                    jnp.asarray(req.max_new_tokens - 1, jnp.int32), h.key)
        finally:
            if saved_pages is not None and self.state.pages is None:
                self.state = dataclasses.replace(self.state,
                                                 pages=saved_pages)
        self.slots[slot] = req
        self._slot_pos[slot] = h.plen
        self._planned[slot] = 0
        self._sched_counter += 1
        self._last_sched[slot] = self._sched_counter
        self.kv.record()
        self._note_peak()

    # ----- preemption & fault recovery ---------------------------------------
    def _victim_order(self, cands: list[int]) -> list[int]:
        """Rank live slots by the configured victim policy (first =
        preempted first).  ``preempt_policy`` may also be a callable
        ``(server, cands) -> ordered cands`` for experimentation."""
        pol = self.preempt_policy
        if callable(pol):
            return list(pol(self, cands))
        if pol == "lru":          # least recently scheduled work
            return sorted(cands, key=lambda i: self._last_sched[i])
        if pol == "fewest_pages":  # cheapest swap traffic
            return sorted(cands,
                          key=lambda i: len(self.manager.slot_pages(i)))
        if pol == "lowest_progress":   # least sunk decode cost
            return sorted(cands, key=lambda i: (
                len(self.slots[i].output)
                / max(self.slots[i].max_new_tokens, 1)))
        raise ValueError(f"unknown preempt_policy {pol!r}")

    def _select_victims(self, shortfall: int) -> list[int]:
        """Fewest victims (in policy order) whose reservations cover
        ``shortfall`` pages; [] when even preempting everyone falls
        short (then waiting on reclamation is the only option)."""
        cands = [i for i, r in enumerate(self.slots) if r is not None]
        out, freed = [], 0
        for i in self._victim_order(cands):
            if freed >= shortfall:
                break
            out.append(i)
            freed += self._reserved.get(i, 0)
        return out if freed >= shortfall else []

    def _preempt_wanted(self) -> bool:
        """Should the pipeline drain so the backlog head can preempt?
        Requires: preemption on, no victim already swapped out
        (anti-thrash: one preemption round resolves before the next
        starts), a free slot, a head blocked on pages, and victims whose
        reservations cover the shortfall."""
        if not (self.preempt_enabled and self._backlog
                and not self._preempted and self._free_slots()):
            return False
        req = self._backlog[0]
        if self._admission_pages_ready(req):
            return False
        worst = self._worst_pages(len(req.prompt), req.max_new_tokens)
        shortfall = worst - (self.manager.capacity
                             - sum(self._reserved.values()))
        return bool(self._select_victims(shortfall))

    def _try_preempt_for(self, req: Request,
                         finished: list[Request]) -> bool:
        """Swap out enough victims for ``req`` to admit.  Only called
        with the pipeline drained (no block in flight), so the gathered
        pages are exactly the harvested positions."""
        if not (self.preempt_enabled and not self._preempted):
            return False
        worst = self._worst_pages(len(req.prompt), req.max_new_tokens)
        shortfall = worst - (self.manager.capacity
                             - sum(self._reserved.values()))
        victims = self._select_victims(shortfall)
        if not victims:
            return False
        for i in victims:
            self._preempt_slot(i, finished)
        return True

    def _preempt_slot(self, i: int, finished: list[Request]) -> None:
        """Swap slot ``i``'s live KV pages to the remote tier and free
        its physical pages + reservation.  Requires no block in flight.
        Shared prefix pages are stashed like private ones and restored
        private — prefix sharing is dropped under pressure (documented
        degradation; tokens are unaffected, only residency).  On an
        unrecoverable transfer fault the victim is shed with a
        structured error instead of poisoning the pool."""
        req = self.slots[i]
        pos = self._slot_pos[i]
        pids = self.manager.slot_pages(i)[:self.manager.pages_for(pos)]
        # deep preemption (threshold 0): stash straight to the cold tier
        # so the remote tier never holds the victim — its hwm stays flat
        # through the preemption round
        tier = (memtiers.COLD if self.cold_park_after_blocks == 0
                else memtiers.REMOTE)
        try:
            with self._mesh_ctx():
                handle = self.swapper.swap_out(self.cache, pids, tier=tier)
        except memtiers.TierTransferError as e:
            self._shed(i, finished, reason="preempt_swap_failed",
                       detail=str(e))
            return
        if tier == memtiers.COLD:
            self.stats["cold_parks"] += 1
        key = np.asarray(jax.device_get(self._req_key(req.uid)))
        self._preempted.append(_Preempted(req=req, pos=pos, handle=handle,
                                          key=key,
                                          stashed_block=self.stats["blocks"]))
        self._evict_slot(i)
        self.stats["preemptions"] += 1
        self.stats["preempted_pages"] += len(pids)
        self.kv.record()

    def _cold_park_sweep(self) -> None:
        """Demote remote-tier stashes whose age (decode blocks since the
        swap-out) exceeds ``cold_park_after_blocks`` to the cold tier.
        Fallible like any transfer: a park that exhausts its retry
        budget leaves the stash in the remote tier (the degradation is
        just capacity not reclaimed — correctness is untouched)."""
        thresh = self.cold_park_after_blocks
        if not thresh or self.swapper is None:   # None or 0: no sweep
            return
        for ps in self._preempted:
            if (ps.handle.tier == memtiers.REMOTE
                    and self.stats["blocks"] - ps.stashed_block >= thresh):
                try:
                    self.swapper.park(ps.handle)
                    self.stats["cold_parks"] += 1
                except memtiers.TierTransferError:
                    pass

    def _evict_slot(self, i: int) -> None:
        """Release slot ``i``'s pages/reservation and deactivate it on
        device (shared by preempt and shed).  The zeroed table row at
        the next block's delta re-points any frozen-position ghost
        writes at the null page."""
        if self.manager is not None:        # dense server: no pool to free
            self.manager.free_slot(i)
        self._reserved.pop(i, None)
        self.slots[i] = None
        self._planned[i] = 0
        self._slot_pos[i] = 0
        st = self.state
        self.state = dataclasses.replace(
            st, active=st.active.at[i].set(False),
            remaining=st.remaining.at[i].set(0))

    def _shed(self, i: int, finished: list[Request], *, reason: str,
              detail: str) -> None:
        """Degradation of last resort: drop slot ``i``'s request with a
        structured error (the server survives; the caller sees why)."""
        req = self.slots[i]
        self._evict_slot(i)
        req.error = {"reason": reason, "detail": detail, "uid": req.uid,
                     "tokens_emitted": len(req.output)}
        self._finalize(req, "shed", finished)
        if self.kv is not None:
            self.kv.record()

    def _shed_preempted(self, ps: _Preempted, finished: list[Request], *,
                        reason: str, detail: str) -> None:
        """Shed a swapped-out victim whose restore failed."""
        if self.swapper is not None and ps.handle is not None:
            self.swapper.release(ps.handle)
        ps.req.error = {"reason": reason, "detail": detail,
                        "uid": ps.req.uid,
                        "tokens_emitted": len(ps.req.output)}
        self._finalize(ps.req, "shed", finished)

    def _service_poison(self, finished: list[Request]) -> None:
        """Shed every slot whose harvest hit non-finite logits — only
        that sequence dies; the rest of the batch decodes on.  Runs with
        the pipeline drained (poisoned slots stall dispatch exactly like
        a pool fault) so eviction can never race an in-flight block."""
        for i, req in list(self._poisoned.items()):
            if self.slots[i] is req:
                self.stats["poison_sheds"] += 1
                self._shed(i, finished, reason="poisoned_logits",
                           detail=f"non-finite logits in decode block "
                                  f"{self.stats['blocks']} at position "
                                  f"{self._slot_pos[i]}")
        self._poisoned.clear()

    def _resume_ready(self, ps: _Preempted) -> bool:
        """A victim resumes only when its remaining worst case fits the
        unreserved pool — the same accounting gate as admission, so a
        resumed sequence can never exhaust the pool either."""
        worst = self._resume_worst(ps)
        return worst <= self.manager.capacity - sum(self._reserved.values())

    def _resume_worst(self, ps: _Preempted) -> int:
        left = ps.req.max_new_tokens - len(ps.req.output)
        return self.manager.pages_for(min(ps.pos + left, self.max_seq))

    def _resume(self, ps: _Preempted, slot: int,
                finished: list[Request]) -> bool:
        """Restore a swapped-out victim into ``slot``: re-allocate pages
        covering its position, scatter the stash back, and re-activate
        the device slot with its original per-slot key — decode then
        continues bit-identically.  False = physically blocked (retry
        later); True = consumed (resumed or shed)."""
        self._reserved[slot] = self._resume_worst(ps)
        try:
            new_ids = self.manager.ensure(slot, ps.pos)
        except MemoryError:
            self._reserved.pop(slot, None)
            return False
        try:
            with self._mesh_ctx():
                if ps.handle.tier != memtiers.REMOTE:
                    # promote-through-remote: a cold-parked stash pays
                    # the cold->remote edge first, then the ordinary
                    # remote->local swap-in — the hierarchy is a path,
                    # not a teleport
                    self.swapper.promote(ps.handle)
                    self.stats["cold_promotes"] += 1
                self.cache = self.swapper.swap_in(self.cache, new_ids,
                                                  ps.handle)
        except memtiers.TierTransferError as e:
            self.manager.free_slot(slot)
            self._reserved.pop(slot, None)
            self._shed_preempted(ps, finished,
                                 reason="resume_swap_failed", detail=str(e))
            return True
        self.manager.note_tokens(slot, ps.pos)
        st = self.state
        self.state = dataclasses.replace(
            st,
            tokens=st.tokens.at[slot, 0].set(ps.req.output[-1]),
            pos=st.pos.at[slot].set(ps.pos),
            active=st.active.at[slot].set(True),
            remaining=st.remaining.at[slot].set(
                ps.req.max_new_tokens - len(ps.req.output)),
            slot_keys=st.slot_keys.at[slot].set(
                jnp.asarray(ps.key, jnp.uint32)))
        self.slots[slot] = ps.req
        self._slot_pos[slot] = ps.pos
        self._planned[slot] = 0
        self._sched_counter += 1
        self._last_sched[slot] = self._sched_counter
        self.stats["resumes"] += 1
        self.kv.record()
        self._note_peak()
        return True

    def _fault_injection_tick(self) -> None:
        """Service an armed pool-exhaustion fault: steal every free page
        into a phantom slot at the armed block, release them
        ``exhaust_blocks`` blocks later (both host-side — the device
        never sees the phantom)."""
        plan = memtiers.active_fault_plan()
        if (self._fault_release_block is not None
                and self.stats["blocks"] >= self._fault_release_block):
            self.manager.free_slot(self._fault_slot)
            self._fault_release_block = None
        if plan is None:
            return
        if plan.take_pool_exhaustion(self.stats["blocks"]):
            steal = self.manager.free_pages * self.page_size
            if steal:
                self.manager.ensure(self._fault_slot, steal)
            self._fault_release_block = (self.stats["blocks"]
                                         + plan.exhaust_blocks)
            self.stats["pool_faults"] += 1

    def _recover_pool_fault(self, finished: list[Request]) -> None:
        """Mid-decode pool exhaustion (injected): with the pipeline
        drained, emergency-preempt one victim so decode can proceed; if
        only one sequence is live there is nothing to preempt FOR it —
        shed it with a structured error (the server survives)."""
        self._pool_fault = False
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return
        order = self._victim_order(live)
        if len(live) == 1:
            self._shed(order[0], finished, reason="pool_exhausted",
                       detail="mid-decode page allocation failed with no "
                              "preemptable victim")
            return
        self._preempt_slot(order[0], finished)

    def _maybe_audit(self) -> None:
        """Debug mode: run the block-pool invariant auditor (refcounts,
        free-list disjointness, table/pool consistency, ledger residency)
        after every scheduling step."""
        if self.audit_every_block and self.paged:
            self.kv.audit()
            self.stats["audits"] += 1

    # ----- decode ------------------------------------------------------------
    def _live_remaining(self, i: int) -> int:
        """Decode tokens slot ``i`` still owes BEYOND every in-flight
        block (host view).  EOS can only shorten this on device, so a
        positive value guarantees the next dispatch is not a ghost block
        for budget reasons (with EOS enabled a slot may still die early —
        tokens stay correct, the block is merely wasted)."""
        req = self.slots[i]
        if req is None:
            return 0
        return req.max_new_tokens - len(req.output) - self._planned[i]

    def _can_dispatch(self) -> bool:
        return any(self._live_remaining(i) > 0 for i in range(self.batch))

    # ----- prefill/decode interference accounting -----------------------------
    def _note_prefill_dispatch(self, ntokens: int) -> None:
        """Record ``ntokens`` of synchronous prefill work dispatched
        while decode work was pending: until the next decode block goes
        out, those tokens ARE the decode stall.  Prefill with no decode
        pending (engine warm-up, idle server) is free and not counted.
        Device-work based, so the metric is deterministic."""
        if self._can_dispatch():
            self._stall_tokens += ntokens

    def _fold_stall(self) -> None:
        """At a decode dispatch, convert the accrued prefill tokens of
        the preceding gap into stalled decode blocks (ceil in
        block-size units) — the bench's worst-case interference gauge:
        monolithic admission of a long prompt charges the whole prompt
        to one gap; the async engine bounds every gap to one chunk."""
        if self._stall_tokens:
            stall = -(-self._stall_tokens // self.block_size)
            self.stats["decode_stall_blocks_max"] = max(
                self.stats["decode_stall_blocks_max"], stall)
            self.stats["decode_stall_blocks_total"] += stall
            self._stall_tokens = 0

    def _record_first_token(self, req: Request) -> None:
        """TTFT sample in decode-block units (queue entry -> first
        token), aggregated into p50/p99 at the end of ``run_once``."""
        req.first_token_block = self.stats["blocks"]
        if req.submitted_block is not None:
            self._ttft_samples.append(req.first_token_block
                                      - req.submitted_block)

    # blocks a narrower bucketed width must persist before the table
    # shrinks: growth is immediate (an unmapped page would corrupt
    # decode), but shrinking only saves attention columns, so it waits
    # out transient dips — e.g. the start of a fresh batch — instead of
    # paying a rebuild + regrow round trip every serving round
    SHRINK_PATIENCE = 8

    def _table_delta(self):
        """Diff the manager's desired per-slot tables against the host
        mirror of the device-resident table.  Steady state returns a
        bucketed ``(slots, cols, pids)`` delta (padding entries carry an
        out-of-range column, dropped by the in-dispatch scatter); a
        width change — growth, or a shrink that outlasted
        ``SHRINK_PATIENCE`` — re-transfers the whole table and returns
        None.  Widths repeat, so executables stay O(log max_pages)
        (``stats["table_rebuilds"]`` counts the transfers)."""
        w_need = _bucket(max(self.manager.max_slot_pages(), 1), 1)
        if w_need < self._table_w:
            self._narrow_blocks += 1
            if self._narrow_blocks < self.SHRINK_PATIENCE:
                w_need = self._table_w      # tolerate the extra null cols
        else:
            self._narrow_blocks = 0
        # desired: live slots' exact tables; evicted slots' rows are
        # ZEROED (the manager no longer knows them), re-pointing a dead
        # slot's frozen-position ghost writes at the null page.  The row
        # must be cleared, not left stale: an inactive slot keeps
        # re-writing its frozen position every dispatch, so a stale row
        # would corrupt a freed page long after its reallocation — the
        # _harvest safety argument only covers the bounded in-flight
        # window between the eviction and this delta.
        desired = self.manager.table(list(range(self.batch)), w_need)
        if w_need != self._table_w:
            self._table_w = w_need
            self._narrow_blocks = 0
            self._mirror = desired
            self.state = dataclasses.replace(
                self.state, pages=self._dev(jnp.asarray(desired)))
            self.stats["table_rebuilds"] += 1
            return None
        rows, cols = np.nonzero(desired != self._mirror)
        self._mirror = desired
        n = len(rows)
        self.stats["table_delta_entries"] += n
        cap = _bucket(max(n, 1), 4)
        d_slots = np.zeros(cap, np.int32)
        d_cols = np.full(cap, w_need, np.int32)  # out of range -> dropped
        d_pids = np.zeros(cap, np.int32)
        d_slots[:n], d_cols[:n] = rows, cols
        d_pids[:n] = desired[rows, cols]
        return (self._dev(jnp.asarray(d_slots)),
                self._dev(jnp.asarray(d_cols)),
                self._dev(jnp.asarray(d_pids)))

    def _count_kv_pages(self, advances: dict) -> None:
        """The paged kernel's work for the block about to go out, per
        layer.  At each step it fetches, for every slot, the table
        columns :func:`pages_fetched` gives for the slot's row in the
        dispatched table and its ``seq_lens`` — the slot's position
        counting in-flight blocks, frozen once the slot stops — and
        nothing for a nulled row.  Of those, a slot that advances
        attends at each step the pages that hold its pooled positions
        (the current token's own key is not pooled)."""
        page = self.page_size
        adv = np.zeros(self.batch, np.int64)
        for i, (_, n) in advances.items():
            adv[i] = n
        start = (np.asarray(self._slot_pos) + np.asarray(self._planned)
                 - adv)
        k = np.arange(self.block_size)
        lens = start[:, None] + np.minimum(k, adv[:, None])   # (B, block)
        self.stats["kv_pages_visited"] += int(pages_fetched(
            self._mirror[:, :1], lens, page, self._table_w, xp=np).sum())
        attended = -(-lens // page) * (k < adv[:, None])
        self.stats["kv_pages_live"] += int(attended.sum())

    def _dispatch_block(self):
        """Dispatch ONE fused decode block without waiting for earlier
        blocks (the donated cache/state buffers chain dispatches in
        order on device).  Page growth covering every planned write is
        folded into this block's table delta; the allocation is
        speculative past in-flight blocks and can only exhaust the pool
        under an injected fault (admission reserved each request's worst
        case) — exhaustion rolls the plan back, latches ``_pool_fault``
        and returns None so ``run_once`` can run emergency recovery."""
        advances: dict[int, tuple[Request, int]] = {}
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            adv = min(self.block_size, self._live_remaining(i))
            if adv > 0:
                advances[i] = (req, adv)
                self._planned[i] += adv
        if self.paged:
            self._fault_injection_tick()
            try:
                for i in advances:
                    self.manager.ensure(i, min(self._slot_pos[i]
                                               + self._planned[i],
                                               self.max_seq))
            except MemoryError:
                for i, (req, adv) in advances.items():
                    self._planned[i] -= adv
                self._pool_fault = True
                return None
            delta = self._table_delta()
            self._count_kv_pages(advances)
            self.kv.record()
            self._note_peak()
            with self._mesh_ctx():
                *outs, self.cache, self.state = self._decode_loop(
                    self.params, self.cache, self.state, delta)
        else:
            with self._mesh_ctx():
                *outs, self.cache, self.state = self._decode_loop(
                    self.params, self.cache, self.state)
        self.cache = self.mem.rest_kv_pool(self.cache)
        self._fold_stall()
        self.stats["dispatches"] += 1
        self.stats["blocks"] += 1
        self.stats["steps"] += self.block_size
        return outs, advances

    def _harvest(self, block, finished: list[Request]) -> None:
        """Sync ONE in-flight block's token harvest (the only host sync
        per block) and fold the outcome back into host bookkeeping:
        slot recycling, refcounted page reclamation, ledger accounting.

        Reclamation while a later block is in flight is safe: a slot
        that died in this block is inactive in every later in-flight
        state, so its only writes are frozen-position ghost writes into
        its own tail page — and any reallocation of that page is either
        fully overwritten (admission prefill writes whole pages) or
        masked until the new owner actually writes each position."""
        outs, advances = block
        with TraceAnnotation("serve.sync"):
            toks_h, valid_h, poison_h, *logits_h = jax.device_get(outs)
        self.stats["host_syncs"] += 1
        for i, (req, adv) in advances.items():
            if self.slots[i] is req:
                self._planned[i] -= adv
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if i in self._poisoned:
                # flagged in an earlier in-flight block: everything this
                # slot produced since is downstream of non-finite state
                continue
            emitted = 0
            bad = False
            for t in range(self.block_size):
                if not valid_h[i, t]:
                    break                 # active mask is monotone per slot
                if poison_h[i, t]:
                    bad = True            # this and later tokens: garbage
                    break
                req.output.append(int(toks_h[i, t]))
                if logits_h:
                    req.logits.append(logits_h[0][i, t])
                emitted += 1
                self.stats["tokens"] += 1
            self._slot_pos[i] += emitted
            if self.paged:
                self.manager.note_tokens(i, self._slot_pos[i])
            if bad:
                # poison stalls dispatch (run_once) and the slot is shed
                # once the pipeline drains — never evict under a block
                # in flight (a recycled slot would steal its harvest)
                self._poisoned[i] = req
                continue
            if (len(req.output) >= req.max_new_tokens
                    or (self.eos_id is not None and req.output
                        and req.output[-1] == self.eos_id)):
                self._finalize(req, "completed", finished)
                self.slots[i] = None       # slot recycled for admission
                self._planned[i] = 0
                if self.paged:
                    self.manager.free_slot(i)   # refcounted reclamation
                    self._reserved.pop(i, None)
        if self.paged:
            self.stats["kv_pages_in_use"] = self.manager.pages_in_use
            self.stats["kv_pages_hwm"] = self.manager.hwm
            self.kv.record()               # per-tier ledger accounting
        self._cold_park_sweep()            # demote over-age stashes

    @functools.partial(jax.profiler.annotate_function, name="serve.run_once")
    def run_once(self, max_blocks: int | None = None) -> list[Request]:
        """Admit queued requests and serve until every admitted request
        completes; returns the finished ones (shed requests too — check
        ``Request.error``).  Requests that arrive (or overflow the slot
        count) while serving are admitted mid-stream.  Non-blocking when
        idle: empty queue + no live slots returns [].

        With ``pipeline`` on, up to two blocks stay in flight: the next
        block is dispatched before the previous block's harvest is
        synced, so host scheduling (token harvest, reclamation,
        admission, the next table delta) overlaps device compute.  When
        preemption is wanted (backlog head starving) or a pool fault is
        latched, dispatching pauses so the pipeline drains first —
        swaps and emergency recovery only run against fully harvested
        state.  ``max_blocks`` bounds the blocks dispatched this call
        (the pipeline still drains before returning), for
        checkpoint-between-blocks callers."""
        finished: list[Request] = []
        self._admit_from_queue(finished)
        inflight: collections.deque = collections.deque()
        dispatched = 0
        while True:
            stall = (self._pool_fault or self._poisoned
                     or self._preempt_wanted() or self._expiry_stall())
            if not stall:
                while (len(inflight) < self.max_inflight
                       and self._can_dispatch()
                       and (max_blocks is None or dispatched < max_blocks)):
                    index = self.stats["blocks"]
                    with TraceAnnotation("serve.dispatch", block=index):
                        blk = self._dispatch_block()
                    if blk is None:      # pool fault latched: drain first
                        break
                    dispatched += 1
                    inflight.append((index, blk))
            if inflight:
                index, blk = inflight.popleft()
                with TraceAnnotation("serve.harvest", block=index):
                    self._harvest(blk, finished)
                self._admit_from_queue(finished,
                                       allow_preempt=not inflight)
                self._maybe_audit()
                continue
            if self._pool_fault:
                self._recover_pool_fault(finished)
                self._maybe_audit()
                continue
            if self._poisoned:
                self._service_poison(finished)
                self._maybe_audit()
                continue
            if max_blocks is not None and dispatched >= max_blocks:
                break
            # idle pipeline: give blocked work one more chance (resume
            # swapped-out victims, preempt for the backlog head)
            self._admit_from_queue(finished, allow_preempt=True)
            self._maybe_audit()
            if not (self._can_dispatch() or self._pool_fault):
                if self._fault_release_block is not None:
                    # nothing can decode, so the block counter will never
                    # reach the release point — the injected exhaustion
                    # window is over by definition; return the pages
                    self.manager.free_slot(self._fault_slot)
                    self._fault_release_block = None
                    self._admit_from_queue(finished, allow_preempt=True)
                    if self._can_dispatch():
                        continue
                if self._orphan_handoffs:
                    # decode idle freezes the block clock, so a lease
                    # measured in blocks can never lapse — force the
                    # reclaim now instead of livelocking the orphans
                    self._lease_watchdog(finished, force=True)
                    self._admit_from_queue(finished, allow_preempt=True)
                    if self._can_dispatch():
                        continue
                break
        if finished:
            self.stats["batches"] += 1
        self.stats["compiles"] = self._compiles()
        if self.swapper is not None:
            self.stats["swap_retries"] = self.swapper.retry_attempts
        self.stats["slow_transfers"] = self.transfer_monitor.flags
        if self._ttft_samples:
            arr = np.asarray(self._ttft_samples, np.float64)
            self.stats["ttft_p50_blocks"] = float(np.percentile(arr, 50))
            self.stats["ttft_p99_blocks"] = float(np.percentile(arr, 99))
        if self._e2e_samples:
            arr = np.asarray(self._e2e_samples, np.float64)
            self.stats["e2e_p50_blocks"] = float(np.percentile(arr, 50))
            self.stats["e2e_p99_blocks"] = float(np.percentile(arr, 99))
        return finished

    def _compiles(self) -> int:
        """Executables compiled across the serving hot path's jit entry
        points — the observable for the O(log) shape-bucketing claim."""
        fns = [self._decode_loop, self._admit_step, self._admit_step_prefix]
        if self.prefill is not None:
            fns += [self.prefill._first_step, self.prefill._cont_step]
        return sum(f._cache_size() for f in fns
                   if f is not None and hasattr(f, "_cache_size"))

    # ----- checkpoint/restart ------------------------------------------------
    def _drain_queue(self) -> None:
        while True:
            try:
                self._backlog.append(self.queue.get_nowait())
            except queue.Empty:
                return

    def snapshot(self) -> dict:
        """Serialize every in-flight sequence — live slots (KV pages
        gathered through the swapper), swapped-out victims (their stash
        verbatim) and queued requests — into a host dict that
        :meth:`restore` (same model/params/seed) rehydrates.  Call
        between ``run_once`` calls (no block in flight).  Feeds
        ``repro.runtime.ft.save_server_snapshot`` for on-disk restart."""
        if not self.paged:
            raise ValueError("snapshot requires the paged server")
        self._drain_queue()
        # engine-crash leftovers must not serialize as leaked pages:
        # cut their leases short (a restart is a new lease epoch),
        # reclaim, and let the victims re-enter as backlog entries
        self._lease_watchdog([], force=True)
        seqs = []

        def entry(req, pos, h=None):
            e = {"uid": req.uid, "prompt": np.asarray(req.prompt, np.int32),
                 "max_new_tokens": req.max_new_tokens,
                 "output": list(req.output), "pos": int(pos),
                 "submitted_block": req.submitted_block,
                 "deadline_blocks": req.deadline_blocks}
            if pos:
                e["k"], e["v"] = h.k, h.v
                if h.k_scale is not None:    # quantized pool: scales too
                    e["k_scale"], e["v_scale"] = h.k_scale, h.v_scale
                # which hierarchy level the stash occupied — restore
                # puts it back in the SAME tier (a cold-parked victim
                # stays cold-parked across a restart)
                e["tier"] = h.tier
            return e

        for i, req in enumerate(self.slots):
            if req is None:
                continue
            pos = self._slot_pos[i]
            pids = self.manager.slot_pages(i)[:self.manager.pages_for(pos)]
            with self._mesh_ctx():
                h = self.swapper.swap_out(self.cache, pids)
            self.swapper.release(h)     # accounting-neutral read-out
            seqs.append(entry(req, pos, h))
        for ps in self._preempted:
            seqs.append(entry(ps.req, ps.pos, ps.handle))
        if self.prefill is not None:
            # a completed handoff is a sequence at pos = plen whose only
            # output is its first token — its staged stash serializes
            # verbatim and restores through the resume path, finishing
            # bit-identically; a mid-chunk prefill re-enters as backlog
            # (prefill is deterministic, so recomputing it is exact)
            for h in self.prefill.ready:
                e = entry(h.req, h.plen, h.handle.materialize())
                e["output"] = [h.first_token]
                seqs.append(e)
            for inf in self.prefill.inflight:
                seqs.append(entry(inf.req, 0))
        for req in self._backlog:
            seqs.append(entry(req, 0))
        seqs.sort(key=lambda e: e["uid"])
        # "blocks" anchors deadline/lease clocks: restore rebases each
        # request's submitted_block so its REMAINING TTL carries over
        return {"seed": self.seed, "uid": self._uid,
                "blocks": self.stats["blocks"], "sequences": seqs}

    def restore(self, snap: dict) -> None:
        """Rehydrate a :meth:`snapshot` into this (idle, same-seed)
        server.  Sequences with decoded positions come back as
        swapped-out stashes — the resume path splices their KV into
        fresh pages and, with per-slot keys, continues bit-identically;
        undecoded ones rejoin the backlog.  Prefix pages restore private
        (sharing re-forms only across NEW admissions)."""
        if snap["seed"] != self.seed:
            raise ValueError(f"snapshot seed {snap['seed']} != server "
                             f"seed {self.seed} (tokens would diverge)")
        if any(r is not None for r in self.slots) or self._preempted \
                or self._backlog or not self.queue.empty() \
                or (self.prefill is not None and not self.prefill.idle):
            raise ValueError("restore requires an idle server")
        self._uid = max(self._uid, int(snap["uid"]))
        snap_blocks = int(snap.get("blocks", 0))
        for s in sorted(snap["sequences"], key=lambda e: e["uid"]):
            req = Request(int(s["uid"]), np.asarray(s["prompt"], np.int32),
                          max_new_tokens=int(s["max_new_tokens"]))
            req.output = [int(t) for t in s["output"]]
            # rebase the deadline clock into THIS server's block counter:
            # the remaining TTL at snapshot time is the remaining TTL now
            # (restart downtime does not run the clock — blocks, not
            # wall time, are the server's SLA unit)
            dl = s.get("deadline_blocks")
            req.deadline_blocks = None if dl is None else int(dl)
            sb = s.get("submitted_block")
            req.submitted_block = (
                self.stats["blocks"] if sb is None
                else self.stats["blocks"] - snap_blocks + int(sb))
            if int(s["pos"]):
                k = np.asarray(s["k"])
                v = np.asarray(s["v"])
                ksc = (np.asarray(s["k_scale"]) if "k_scale" in s else None)
                vsc = (np.asarray(s["v_scale"]) if "v_scale" in s else None)
                arrs = [a for a in (k, v, ksc, vsc) if a is not None]
                handle = SwapHandle(
                    page_count=k.shape[1], k=k, v=v,
                    nbytes=sum(a.size * a.dtype.itemsize for a in arrs),
                    k_scale=ksc, v_scale=vsc,
                    tier=s.get("tier", memtiers.REMOTE))
                self.swapper.adopt(handle)
                key = np.asarray(jax.device_get(self._req_key(req.uid)))
                self._preempted.append(_Preempted(
                    req=req, pos=int(s["pos"]), handle=handle, key=key,
                    stashed_block=self.stats["blocks"]))
            else:
                self._backlog.append(req)
                self._pending_add(req)

    # ----- accounting --------------------------------------------------------
    def kv_bytes_in_use(self) -> int:
        """Live KV footprint: allocated pages only (paged) or the whole
        dense slab (which is resident regardless of occupancy)."""
        if not self.paged:
            return memory.tree_bytes(self.cache)
        kp = self.cache["k_pages"]
        sc = self.cache.get("k_scale")
        per_page = self.manager.bytes_per_page(
            kp.shape[2], kp.shape[4], kp.dtype.itemsize,
            num_layers=kp.shape[0],
            scale_itemsize=(sc.dtype.itemsize if sc is not None else 0))
        return self.manager.pages_in_use * per_page

    def kv_bytes_capacity(self) -> int:
        return memory.tree_bytes(self.cache)

    def tier_stats(self) -> dict:
        """Per-tier residency snapshot (feeds ``BENCH_serve.json``)."""
        return self.mem.ledger.snapshot()

    def tier_stats_peak(self) -> dict:
        """Per-tier snapshot captured mid-flight at peak pool occupancy
        (the end-of-run ``tier_stats`` is drained: ``kv_pool`` reads 0
        after every page is reclaimed)."""
        return self.tiers_peak or self.tier_stats()