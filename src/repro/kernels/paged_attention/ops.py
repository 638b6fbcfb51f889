"""jit'd wrappers for paged decode attention + block-pool management.

Two halves of the FengHuang block-pool KV cache:

* :func:`attend` / :func:`attend_ref` — the kernel-side read path.  The
  Pallas kernel (scalar-prefetched page tables) serves TPU; the gather
  oracle is the jittable fallback everywhere else.  Pick once per backend
  with :func:`use_pallas_kernel`; under a mesh the kernel runs per head
  shard (:func:`sharded_paged_attention`).
* :class:`BlockManager` — the host-side allocator.  It owns ONLY the
  bookkeeping (free list, per-slot page lists, lengths, accounting); the
  stacked ``(L, P, Hkv, page, hd)`` device pools live in the serving
  cache and are donated through every dispatch, with all KV writes done
  on device as batched scatters (one per decode step covering every
  layer and slot, one per prefill covering the whole prompt chunk).

Page 0 is the reserved **null page**: table padding and the write slots
of idle/finished sequences point at it, so garbage reads are masked by
``seq_lens`` and garbage writes land where no sequence ever looks.  The
kernel takes a row whose first column is the null page for a dead slot
and fetches none of its pages (``kernel.pages_fetched``).
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import (gather_pages, gather_scales,
                                               paged_attention_ref)

# Logical specs for the block pool under tensor parallelism: the KV-heads
# axis is "model"-sharded (each device owns its head shard of EVERY
# page), page ids and per-slot tables are replicated host bookkeeping.
# Quantized pools carry per-(head, token-slot) dequant scales that shard
# exactly like their pages (head axis on "model").
STACKED_POOL_SPEC = P(None, None, "model", None, None)   # (L, P, Hkv, pg, hd)
STACKED_SCALE_SPEC = P(None, None, "model", None)        # (L, P, Hkv, pg)
GATHERED_KV_SPEC = P(None, "model", None, None)          # (B, Hkv, n*pg, hd)
GATHERED_SCALE_SPEC = P(None, "model", None)             # (B, Hkv, n*pg)


def gather_pages_sharded(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """:func:`gather_pages` with the KV-heads axis constrained to stay
    "model"-sharded: the gather indexes only the (replicated) page axis,
    so under a mesh each device materializes just its head shard of the
    per-sequence view — no cross-device KV movement on the decode read
    path.  Outside a mesh context the constraint is a no-op."""
    from repro.runtime.sharding import maybe_constraint
    return maybe_constraint(gather_pages(pages, page_table),
                            GATHERED_KV_SPEC)


def gather_scales_sharded(scales: jax.Array,
                          page_table: jax.Array) -> jax.Array:
    """:func:`gather_scales` with the head axis kept "model"-sharded,
    mirroring :func:`gather_pages_sharded` for the dequant scales."""
    from repro.runtime.sharding import maybe_constraint
    return maybe_constraint(gather_scales(scales, page_table),
                            GATHERED_SCALE_SPEC)


@functools.lru_cache(maxsize=None)
def use_pallas_kernel() -> bool:
    """Backend selection for the serving hot path, resolved once: the
    Mosaic kernel needs a TPU; everywhere else the gather-based oracle is
    the jittable (and bit-compatible) implementation."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def attend(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
           page_table: jax.Array, seq_lens: jax.Array,
           extra_kv: tuple[jax.Array, jax.Array] | None = None,
           k_scales: jax.Array | None = None,
           v_scales: jax.Array | None = None, *,
           interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, d) single decode token -> (B, Hkv, G, d)."""
    return paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           extra_kv=extra_kv, k_scales=k_scales,
                           v_scales=v_scales, interpret=interpret)


def sharded_paged_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            seq_lens: jax.Array, *,
                            extra_kv: tuple[jax.Array, jax.Array] | None = None,
                            k_scales: jax.Array | None = None,
                            v_scales: jax.Array | None = None) -> jax.Array:
    """:func:`paged_attention` under the ambient mesh.

    XLA cannot partition a ``pallas_call``, so under a mesh each device
    runs the kernel on its own KV-head shard inside ``jax.shard_map``
    over the ``"model"`` axis: q, pools, scales and ``extra_kv`` enter
    split by KV head, page table and lengths replicated — every head's
    attention is local to the device holding its pages.  Outside a mesh
    (or on a one-device mesh) the kernel is called directly."""
    from repro.runtime.sharding import ambient_mesh, mesh_axis_sizes
    arrays = {"q": q, "k_pages": k_pages, "v_pages": v_pages,
              "page_table": page_table, "seq_lens": seq_lens}
    if extra_kv is not None:
        arrays["k0"], arrays["v0"] = extra_kv
    if k_scales is not None:
        arrays["k_scales"], arrays["v_scales"] = k_scales, v_scales

    def call(a):
        kv0 = (a["k0"], a["v0"]) if "k0" in a else None
        return paged_attention(a["q"], a["k_pages"], a["v_pages"],
                               a["page_table"], a["seq_lens"], extra_kv=kv0,
                               k_scales=a.get("k_scales"),
                               v_scales=a.get("v_scales"))

    mesh = ambient_mesh()
    if mesh is None or mesh_axis_sizes(mesh).get("model", 1) == 1:
        return call(arrays)
    # the head axis is dim 1 of every split operand: q/out (B, Hkv, G, d),
    # pools (P, Hkv, page, d), scales (P, Hkv, page), extra (B, Hkv, d)
    specs = {k: (P() if k in ("page_table", "seq_lens")
                 else P(None, "model")) for k in arrays}
    return jax.shard_map(call, mesh=mesh, in_specs=(specs,),
                         out_specs=P(None, "model"),
                         check_vma=False)(arrays)


def attend_ref(q, k_pages, v_pages, page_table, seq_lens, extra_kv=None,
               k_scales=None, v_scales=None):
    return paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens,
                               extra_kv=extra_kv, k_scales=k_scales,
                               v_scales=v_scales)


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to map ``tokens`` positions."""
    return -(-tokens // page_size)


class BlockPoolAuditError(AssertionError):
    """An invariant of the block-pool bookkeeping is violated (refcount
    drift, free-list corruption, table/pool inconsistency)."""


class BlockManager:
    """Host-side page allocator for the device-resident block pool.

    Sequences (keyed by serving slot) own ordered lists of fixed-size
    pages from a global pool — the FengHuang remote tier holds the pool;
    per-sequence page tables are the Tensor Prefetcher's routing
    metadata.  Allocation happens at block boundaries (a slot is grown to
    cover its next decode block in one call); reclamation returns a
    finished slot's pages to the free list in LIFO order so hot pages are
    reused first.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the null page)")
        if page_size < 1:
            raise ValueError("page_size must be positive")
        self.num_pages = num_pages
        self.page_size = page_size
        self._free = list(range(num_pages - 1, 0, -1))  # page 0 = null page
        self.pages: dict[int, list[int]] = {}
        self.lens: dict[int, int] = {}
        self.hwm = 0                    # pages-in-use high-water mark
        # prefix caching: pages shared across slots carry a refcount and
        # (for prompt-prefix pages) an entry in the prefix index keyed by
        # the exact token bytes they cover.  A page returns to the free
        # list only when its last owner releases it.
        self.refcount: dict[int, int] = {}
        self._prefix_index: dict[bytes, int] = {}
        self._page_key: dict[int, bytes] = {}
        # prefill->decode handoffs: pages detached from a prefill slot
        # and parked under an opaque token until a decode slot adopts
        # them.  Handoff pages are owned by NO slot but stay refcounted
        # (the handoff IS the owner) — audit() treats each in-flight
        # handoff as a pseudo-slot.
        self._handoffs: dict[int, tuple[list[int], int]] = {}
        self._next_handoff = 1

    # ----- capacity ---------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is never handed out)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.capacity - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return pages_for(tokens, self.page_size)

    def can_fit(self, slot: int, tokens: int) -> bool:
        """Would :meth:`ensure`'ing ``tokens`` for ``slot`` succeed now?"""
        have = len(self.pages.get(slot, ()))
        return self.pages_for(tokens) - have <= len(self._free)

    # ----- allocate / reclaim ----------------------------------------------
    def ensure(self, slot: int, tokens: int) -> list[int]:
        """Grow ``slot`` so positions ``[0, tokens)`` are mapped; returns
        the newly allocated page ids (possibly empty).  Raises
        ``MemoryError`` when the pool cannot cover the growth."""
        table = self.pages.setdefault(slot, [])
        need = self.pages_for(tokens) - len(table)
        if need > len(self._free):
            raise MemoryError(
                f"page pool exhausted: slot {slot} needs {need} more "
                f"page(s) for {tokens} tokens, {len(self._free)} free of "
                f"{self.capacity}")
        new = [self._free.pop() for _ in range(max(need, 0))]
        for p in new:
            self.refcount[p] = 1
        table.extend(new)
        self.hwm = max(self.hwm, self.pages_in_use)
        return new

    def adopt(self, slot: int, page_ids: list[int]) -> None:
        """Map ``slot``'s leading table entries onto already-allocated
        pages (prompt-prefix sharing): each adopted page's refcount rises
        by one and NO pool page is consumed.  Only valid on a fresh slot
        — adopted pages must precede any privately allocated ones so the
        table stays position-ordered."""
        table = self.pages.setdefault(slot, [])
        if table:
            raise ValueError(
                f"slot {slot} already owns pages; prefix pages must lead")
        for p in page_ids:
            if self.refcount.get(p, 0) < 1:
                raise ValueError(f"page {p} is not live; cannot adopt")
            self.refcount[p] += 1
        table.extend(page_ids)

    def note_tokens(self, slot: int, tokens: int) -> None:
        """Record that ``slot`` now holds ``tokens`` written positions
        (drives the fragmentation accounting; monotone per slot)."""
        self.lens[slot] = max(self.lens.get(slot, 0), tokens)

    def _release_pages(self, page_ids: list[int]) -> None:
        """Drop one reference from each page (reverse order so LIFO
        reuse favors hot pages).  Pages still referenced by another
        sharer survive; a page whose last reference drops returns to the
        free list and leaves the prefix index."""
        for p in reversed(page_ids):
            rc = self.refcount.get(p, 1) - 1
            if rc > 0:
                self.refcount[p] = rc
                continue
            self.refcount.pop(p, None)
            self._free.append(p)
            key = self._page_key.pop(p, None)
            if key is not None:
                self._prefix_index.pop(key, None)

    def free_slot(self, slot: int) -> None:
        """Release every page owned by ``slot`` (EOS / eviction)."""
        self._release_pages(self.pages.pop(slot, []))
        self.lens.pop(slot, None)

    # ----- prefill->decode handoffs ------------------------------------------
    def detach_to_handoff(self, slot: int) -> int:
        """Detach ``slot``'s pages into a handoff token: the slot
        disappears, its pages keep their refcounts (ownership moves to
        the handoff), and the returned token later rebinds them to a
        decode slot via :meth:`adopt_from_handoff`.  This is the
        allocator half of the prefill->decode page handoff — no page is
        copied, freed, or reallocated across the engine boundary."""
        if slot not in self.pages:
            raise KeyError(f"slot {slot} owns no pages to hand off")
        token = self._next_handoff
        self._next_handoff += 1
        self._handoffs[token] = (self.pages.pop(slot),
                                 self.lens.pop(slot, 0))
        return token

    def adopt_from_handoff(self, slot: int, token: int) -> list[int]:
        """Rebind a handoff's pages to a fresh decode ``slot`` (refcounts
        unchanged — ownership transfers back from the handoff).  Returns
        the page ids, now ``slot``'s table."""
        if token not in self._handoffs:
            raise KeyError(f"unknown handoff token {token}")
        if self.pages.get(slot):
            raise ValueError(
                f"slot {slot} already owns pages; cannot adopt handoff")
        pages, tokens = self._handoffs.pop(token)
        self.pages[slot] = pages
        if tokens:
            self.note_tokens(slot, tokens)
        return list(pages)

    def release_handoff(self, token: int) -> None:
        """Drop an in-flight handoff without adopting it (shed /
        restore-into-snapshot): its pages lose the handoff's reference
        exactly like :meth:`free_slot` releases a slot's."""
        pages, _ = self._handoffs.pop(token, ([], 0))
        self._release_pages(pages)

    @property
    def handoff_pages(self) -> int:
        """Pages currently parked in prefill->decode handoffs."""
        return sum(len(p) for p, _ in self._handoffs.values())

    # ----- prompt-prefix index ----------------------------------------------
    def register_prefix(self, key: bytes, page_id: int) -> None:
        """Publish a fully written prompt page under the exact token
        bytes it covers (position-dependent: the key is the whole padded
        prompt up to and including this page).  First writer wins; the
        entry lives exactly as long as the page has owners."""
        if key in self._prefix_index:
            return
        if self.refcount.get(page_id, 0) < 1:
            raise ValueError(f"page {page_id} is not live; cannot index")
        self._prefix_index[key] = page_id
        self._page_key[page_id] = key

    def lookup_prefix(self, key: bytes) -> int | None:
        return self._prefix_index.get(key)

    @property
    def shared_pages(self) -> int:
        """Logical pages served by sharing beyond their physical count
        (sum of refcount - 1 over multiply-owned pages)."""
        return sum(rc - 1 for rc in self.refcount.values() if rc > 1)

    # ----- tables -----------------------------------------------------------
    def slot_pages(self, slot: int) -> list[int]:
        return list(self.pages.get(slot, ()))

    def max_slot_pages(self) -> int:
        return max((len(t) for t in self.pages.values()), default=0)

    def table(self, slots: list[int], n_pages: int) -> np.ndarray:
        """(len(slots), n_pages) int32 page table, null-page padded."""
        out = np.zeros((len(slots), n_pages), np.int32)
        for i, s in enumerate(slots):
            t = self.pages.get(s, [])[:n_pages]
            out[i, : len(t)] = t
        return out

    # ----- invariants -------------------------------------------------------
    def audit(self) -> dict:
        """Cross-check every allocator invariant; raises
        :class:`BlockPoolAuditError` on the first violation, returns a
        summary dict when clean.

        Invariants: the null page is never owned or free-listed; free
        pages are unique, in range, and disjoint from every table; a
        slot's table holds no duplicate pages; each live page's refcount
        equals its owner count across tables; free + allocated ==
        capacity; the prefix index and its page->key inverse agree and
        only reference live pages; recorded lengths fit their tables;
        the high-water mark bounds current occupancy.  In-flight
        prefill->decode handoffs participate as pseudo-slots (owned by
        no slot, refcounted by the handoff).  Called after every decode
        block in the server's audit mode — the race/corruption detector
        for the whole paged stack."""
        def fail(msg: str):
            raise BlockPoolAuditError(f"block-pool audit: {msg}")

        free = self._free
        free_set = set(free)
        if len(free_set) != len(free):
            fail(f"free list holds duplicates ({len(free) - len(free_set)})")
        bad = [p for p in free_set if not 1 <= p < self.num_pages]
        if bad:
            fail(f"free list holds out-of-range/null pages {sorted(bad)}")
        owners: dict[int, int] = {}
        # in-flight prefill->decode handoffs are pseudo-slots: their
        # pages are owned by no slot but must stay refcounted, in range,
        # and disjoint from the free list until adopted or released
        tables = list(self.pages.items()) + [
            (f"handoff:{tok}", pages)
            for tok, (pages, _) in self._handoffs.items()]
        for slot, table in tables:
            if len(set(table)) != len(table):
                fail(f"slot {slot} maps a page twice: {table}")
            for p in table:
                if not 1 <= p < self.num_pages:
                    fail(f"slot {slot} maps out-of-range/null page {p}")
                if p in free_set:
                    fail(f"page {p} is both free and owned by slot {slot}")
                owners[p] = owners.get(p, 0) + 1
        if set(self.refcount) != set(owners):
            fail(f"refcount keys {sorted(self.refcount)} != allocated "
                 f"pages {sorted(owners)}")
        for p, rc in self.refcount.items():
            if rc != owners[p]:
                fail(f"page {p} refcount {rc} != owner count {owners[p]}")
        if len(free) + len(owners) != self.capacity:
            fail(f"{len(free)} free + {len(owners)} allocated != "
                 f"capacity {self.capacity}")
        for key, p in self._prefix_index.items():
            if self._page_key.get(p) != key:
                fail(f"prefix index maps {key!r} -> page {p} but the "
                     f"inverse disagrees")
            if self.refcount.get(p, 0) < 1:
                fail(f"prefix index references dead page {p}")
        for p, key in self._page_key.items():
            if self._prefix_index.get(key) != p:
                fail(f"page-key inverse {p} -> {key!r} missing from the "
                     f"prefix index")
        for slot, n in self.lens.items():
            cover = len(self.pages.get(slot, ())) * self.page_size
            if n > cover:
                fail(f"slot {slot} records {n} tokens but its table "
                     f"covers only {cover}")
        for tok, (pages, n) in self._handoffs.items():
            if n > len(pages) * self.page_size:
                fail(f"handoff {tok} records {n} tokens but covers only "
                     f"{len(pages) * self.page_size}")
        if self.hwm < self.pages_in_use:
            fail(f"hwm {self.hwm} < pages in use {self.pages_in_use}")
        if self.hwm > self.capacity:
            fail(f"hwm {self.hwm} > capacity {self.capacity} (occupancy "
                 f"exceeded the provisioned pool)")
        return {"pages_in_use": self.pages_in_use,
                "free_pages": len(free), "slots": len(self.pages),
                "shared_pages": self.shared_pages,
                "handoff_pages": self.handoff_pages}

    # ----- accounting -------------------------------------------------------
    def bytes_per_page(self, kv_heads: int, head_dim: int,
                       itemsize: int = 2, num_layers: int = 1,
                       scale_itemsize: int = 0) -> int:
        """Bytes ONE page occupies across both pools and all layers.

        ``scale_itemsize`` > 0 adds the per-(token-slot, head) dequant
        scale storage of a quantized pool (one scale per position per KV
        head per pool), so quantized accounting charges TRUE bytes —
        scales included — and ``capacity_reduction`` stays comparable."""
        return (2 * num_layers * self.page_size * kv_heads
                * (head_dim * itemsize + scale_itemsize))

    def fragmentation(self) -> float:
        """Fraction of in-use page slots holding no live token (tail
        waste of partially filled last pages).  With prefix sharing the
        logical token count can exceed the physical slot count (that is
        the point), so the result is clamped at 0."""
        in_use = self.pages_in_use * self.page_size
        if not in_use:
            return 0.0
        live = sum(min(self.lens.get(s, 0), len(t) * self.page_size)
                   for s, t in self.pages.items())
        return max(0.0, 1.0 - live / in_use)


# The deprecated host-driven ``PagePool`` wrapper that used to live here
# is gone; host-side pool experiments go through
# ``repro.memory.policies.BlockPoolResidency`` (same BlockManager
# bookkeeping, batched ``append_block`` writes, ledger accounting).
