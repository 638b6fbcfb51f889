"""Residency policies: the pluggable seam between tensor classes and the
tier hierarchy.

A :class:`ResidencyPolicy` answers two questions for one *tensor class*
(stacked layer weights, a dense KV cache, a block-pool KV pool, expert
banks): **where does it live at rest** (``tier`` + ``place``) and **how
does it move through local memory while computing** (policy-specific:
the double-buffered prefetch window, the scan-carry offload, the
block-pool page tables, the routed-expert gather).  ``pick_tier``
is the access-frequency face of the first question: given observed
access stats, a policy may answer with a *colder* hierarchy level than
its home tier (long-idle pools and rarely-routed expert banks demote
to ``cold``).  The
:class:`~repro.memory.orchestrator.MemoryOrchestrator` binds classes to
policies and owns the scan transforms the policies ride.

Concrete policies:

* :class:`PinLocal` — default; tensors stay in local HBM.
* :class:`DoubleBufferPrefetch` — stacked layer weights at rest in the
  remote tier, paged per layer with a lookahead-w double buffer (the
  paper's Tensor Prefetcher, w=1 materialized).
* :class:`OffloadBetweenSteps` — KV pools parked in the remote tier
  between dispatches, one layer's slice local at a time in the scan
  carry.
* :class:`BlockPoolResidency` — block-pool paged KV: wraps
  :class:`~repro.kernels.paged_attention.ops.BlockManager` bookkeeping
  (free list / tables / lengths / refcounts / hwm / fragmentation) and
  reports through the shared ledger — prefix-shared pages count once, so
  the ``kv_pool`` class reflects physical residency; optionally owns
  host-side pools for host-driven experiments (the role the deleted
  ``PagePool`` played).
* :class:`TopKExpertPrefetch` — MoE expert banks at rest in the remote
  tier; only the rows routing selects are paged in per decode block.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import BlockManager
from repro.memory import tiers
from repro.memory.accounting import MemoryLedger, tree_bytes


@dataclasses.dataclass(frozen=True)
class PagerConfig:
    """FengHuang paging policy knobs (the per-model policy matrix).

    enabled          — page stacked layer weights through the remote tier.
    lookahead        — prefetch window in layers (paper w=1).  Only w=1 is
                       materialized as an explicit double buffer; deeper
                       windows are left to XLA's scheduler, which may hoist
                       further copy-starts.
    offload_kv       — keep the KV cache in the remote tier between steps,
                       paging per-layer pages in during attention.
    page_experts     — MoE expert banks live in the remote tier; decode
                       pages in only the routed (top-k) expert rows.
    donate_evicted   — donate the consumed buffer (eviction is implicit:
                       the buffer is dead after the layer computes).
    """

    enabled: bool = False
    lookahead: int = 1
    offload_kv: bool = False
    page_experts: bool = False
    donate_evicted: bool = True


@runtime_checkable
class ResidencyPolicy(Protocol):
    """Where a tensor class lives at rest, and how it is placed there.

    ``sharding(mesh, spec)`` is the mesh-aware face of the same answer:
    a :class:`~jax.sharding.NamedSharding` carrying BOTH the partition
    spec and the policy's tier resolved to the memory kind the current
    backend exposes — policies emit NamedShardings, never bare kinds.
    """

    tier: str

    def place(self, tree: Any) -> Any:
        """Move ``tree`` into the policy's home tier (eager)."""
        ...

    def sharding(self, mesh, spec):
        """NamedSharding placing one leaf in the policy's tier."""
        ...

    def pick_tier(self, access_stats: dict | None = None) -> str:
        """Hierarchy level this class should occupy given how it is
        being accessed (``access_stats`` keys are policy-specific:
        ``idle_steps`` for between-step offload, ``route_fraction`` for
        expert banks).  The home ``tier`` when stats are absent or
        unremarkable; a colder tier when access frequency justifies the
        bandwidth gap."""
        ...


@dataclasses.dataclass(frozen=True)
class PinLocal:
    """Default policy: device-resident, placement is the identity."""

    tier: str = tiers.LOCAL

    def place(self, tree: Any) -> Any:
        return tree

    def sharding(self, mesh, spec):
        return tiers.tier_sharding(mesh, spec, self.tier)

    def pick_tier(self, access_stats: dict | None = None) -> str:
        return self.tier


@dataclasses.dataclass(frozen=True)
class DoubleBufferPrefetch:
    """Stacked layer weights at rest in the remote tier, streamed through
    a (1 + lookahead)-layer local window by the paged layer scan."""

    lookahead: int = 1
    tier: str = tiers.REMOTE

    def place(self, tree: Any) -> Any:
        return tiers.host_put(tree)

    def sharding(self, mesh, spec):
        return tiers.tier_sharding(mesh, spec, self.tier)

    def pick_tier(self, access_stats: dict | None = None) -> str:
        # the prefetch window touches every layer every step — layer
        # weights never go colder than their home tier
        return self.tier


@dataclasses.dataclass(frozen=True)
class OffloadBetweenSteps:
    """KV pools at rest in the remote tier between dispatches; decode
    pages one layer's pool through local memory at a time (the scan
    carry of ``paged_scan_cache``).  Small leaves (page tables, lengths)
    stay local — only ``pool_keys`` move."""

    pool_keys: tuple[str, ...] = ("k_pages", "v_pages", "k_scale", "v_scale")
    tier: str = tiers.REMOTE
    # a pool untouched for this many steps (a long-idle prefix page set,
    # a parked conversation) belongs in the cold tier
    cold_after_idle_steps: int = 64

    def place(self, tree: Any) -> Any:
        return {k: (tiers.host_put(v) if k in self.pool_keys else v)
                for k, v in tree.items()}

    def sharding(self, mesh, spec, *, key: str | None = None):
        """Pool leaves live remote; bookkeeping leaves stay local."""
        tier = self.tier if (key is None or key in self.pool_keys) \
            else tiers.LOCAL
        return tiers.tier_sharding(mesh, spec, tier)

    def pick_tier(self, access_stats: dict | None = None) -> str:
        """Access-frequency placement: a pool idle for
        ``cold_after_idle_steps`` dispatches demotes to cold (it pays
        the flash-bandwidth gap once on resume instead of holding remote
        capacity every step it is not read)."""
        if (access_stats
                and access_stats.get("idle_steps", 0)
                >= self.cold_after_idle_steps):
            return tiers.COLD
        return self.tier


class BlockPoolResidency:
    """Block-pool paged KV residency.

    Wraps the host-side :class:`BlockManager` (allocation happens at
    block boundaries, reclamation on EOS/eviction) and reports live
    pool bytes into the shared :class:`MemoryLedger`.  The stacked
    device pools normally live in the serving cache and are donated
    through every dispatch; pass ``kv_heads``/``head_dim`` to own small
    host-side pools instead (host-driven experiments and tests), written
    with :meth:`append_block` — ONE batched scatter per block of tokens.
    """

    tensor_class = "kv_pool"

    def __init__(self, num_pages: int, page_size: int, *,
                 kv_heads: int | None = None, head_dim: int | None = None,
                 dtype=jnp.bfloat16, bytes_per_page: int | None = None,
                 tier: str = tiers.LOCAL,
                 ledger: MemoryLedger | None = None,
                 shard_factor: int = 1):
        self.manager = BlockManager(num_pages, page_size)
        self.page_size = page_size
        self.tier = tier
        self.ledger = ledger
        # model-axis shards of the device pools: the kv-heads axis is
        # "model"-sharded under tensor parallelism, so ONE device holds
        # 1/shard_factor of every page's bytes — ledger residency is
        # recorded per shard (comparable to the per-GPU simulator)
        self.shard_factor = max(int(shard_factor), 1)
        self._bytes_per_page = bytes_per_page
        self.k = self.v = None
        if kv_heads is not None and head_dim is not None:
            # the serving pool layout, (P, Hkv, page, hd)
            self.k = jnp.zeros((num_pages, kv_heads, page_size, head_dim),
                               dtype)
            self.v = jnp.zeros((num_pages, kv_heads, page_size, head_dim),
                               dtype)
            if bytes_per_page is None:
                self._bytes_per_page = self.manager.bytes_per_page(
                    kv_heads, head_dim, jnp.dtype(dtype).itemsize)

    def place(self, tree: Any) -> Any:
        return tree

    def sharding(self, mesh, spec):
        return tiers.tier_sharding(mesh, spec, self.tier)

    def pick_tier(self, access_stats: dict | None = None) -> str:
        # the live pool is read every attention step; only its
        # preemption stashes move down-hierarchy (PageSwapper.park)
        return self.tier

    def bind_kv_shape(self, kv_heads: int, head_dim: int, itemsize: int,
                      num_layers: int = 1, scale_itemsize: int = 0) -> None:
        """Derive per-page bytes from the served cache's shape (single
        source: :meth:`BlockManager.bytes_per_page`).  Quantized pools
        pass ``scale_itemsize`` (bf16 scales -> 2) so the ledger's
        ``kv_pool`` line reports TRUE quantized bytes, scales included —
        keeping ``capacity_reduction`` Table-4.3-comparable."""
        self._bytes_per_page = self.manager.bytes_per_page(
            kv_heads, head_dim, itemsize, num_layers=num_layers,
            scale_itemsize=scale_itemsize)

    # ----- bookkeeping (delegated) -----------------------------------------
    @property
    def capacity(self) -> int:
        return self.manager.capacity

    @property
    def pages_in_use(self) -> int:
        return self.manager.pages_in_use

    @property
    def hwm(self) -> int:
        return self.manager.hwm

    @property
    def shared_pages(self) -> int:
        """Logical pages served beyond their physical count by prompt-
        prefix sharing (refcounted pages count once toward residency —
        the ledger's ``kv_pool`` class shrinks by exactly this times the
        page bytes under a shared system prompt)."""
        return self.manager.shared_pages

    def fragmentation(self) -> float:
        return self.manager.fragmentation()

    def record(self) -> None:
        """Push the pool's live footprint into the ledger (per shard:
        heads-sharded pools hold 1/shard_factor of each page per device)."""
        if self.ledger is not None and self._bytes_per_page:
            self.ledger.record(self.tier, self.tensor_class,
                               self.manager.pages_in_use
                               * self._bytes_per_page
                               // self.shard_factor)

    def audit(self) -> dict:
        """Full invariant audit: the manager's allocator checks
        (:meth:`BlockManager.audit`) plus the ledger cross-check — the
        recorded ``kv_pool`` residency must equal the physical
        pages-in-use times per-page bytes (per shard).  Only meaningful
        right after :meth:`record`; callers audit at block boundaries
        where that holds."""
        summary = self.manager.audit()
        if self.ledger is not None and self._bytes_per_page:
            want = (self.manager.pages_in_use * self._bytes_per_page
                    // self.shard_factor)
            got = self.ledger.classes(self.tier).get(self.tensor_class)
            if got is not None and got != want:
                from repro.kernels.paged_attention.ops import \
                    BlockPoolAuditError
                raise BlockPoolAuditError(
                    f"ledger residency drift: {self.tier}/"
                    f"{self.tensor_class} records {got} bytes but "
                    f"{self.manager.pages_in_use} live pages x "
                    f"{self._bytes_per_page} bytes / {self.shard_factor} "
                    f"shard(s) = {want}")
        return summary

    # ----- host-side pools (experiments/tests) ------------------------------
    def alloc_seq(self, uid: int) -> None:
        self.manager.pages.setdefault(uid, [])
        self.manager.lens.setdefault(uid, 0)

    def append_block(self, uid: int, k_blk: jax.Array,
                     v_blk: jax.Array) -> None:
        """k_blk/v_blk: (T, kv_heads, head_dim) — T tokens appended with a
        single batched scatter per pool."""
        if self.k is None:
            raise ValueError("host-side pools not initialised; construct "
                             "with kv_heads/head_dim")
        t = k_blk.shape[0]
        pos0 = self.manager.lens.get(uid, 0)
        self.manager.ensure(uid, pos0 + t)
        table = jnp.asarray(self.manager.pages[uid], jnp.int32)
        pos = pos0 + jnp.arange(t)
        pids = table[pos // self.page_size]
        slots = pos % self.page_size
        self.k = self.k.at[pids, :, slots].set(k_blk.astype(self.k.dtype))
        self.v = self.v.at[pids, :, slots].set(v_blk.astype(self.v.dtype))
        self.manager.lens[uid] = pos0 + t
        self.record()

    def free_seq(self, uid: int) -> None:
        self.manager.free_slot(uid)
        self.record()

    def batch_tables(self, uids: list[int], n_pages: int) -> jax.Array:
        return jnp.asarray(self.manager.table(uids, n_pages), jnp.int32)

    def batch_lens(self, uids: list[int]) -> jax.Array:
        return jnp.asarray([self.manager.lens.get(u, 0) for u in uids],
                           jnp.int32)


@dataclasses.dataclass
class TopKExpertPrefetch:
    """MoE expert paging: banks at rest in the remote tier, only routed
    rows local.

    The expert banks (``wi``/``wg``/``wo``, each with a leading expert
    axis) are the workload class where disaggregated memory pays off
    most: a top-k router touches k of E experts per token, so decode
    needs only ``tokens x k`` rows (+ one in-flight staging row per
    bank) in local memory — ``(top_k + 1) / num_experts`` of the dense
    footprint for single-slot decode.  Routing is data-dependent, so
    unlike layer weights there is no lookahead window: the gather *is*
    the prefetch, issued as soon as the router's top-k lands.
    """

    num_experts: int
    top_k: int
    bank_keys: tuple[str, ...] = ("wi", "wg", "wo")
    tier: str = tiers.REMOTE
    # an expert routed to fewer than this fraction of tokens earns cold
    # residency (rarely-read, read-mostly: the High-Bandwidth-Flash
    # tenant profile)
    cold_route_fraction: float = 0.02
    ledger: MemoryLedger | None = None
    tensor_class = "expert_weights"

    def matches(self, path: str) -> bool:
        """Leaf-path selector for expert-bank leaves inside a stacked
        layer pytree (``...['moe']['wi']`` etc.)."""
        return "moe" in path and any(path.endswith(f"['{k}']")
                                     for k in self.bank_keys)

    def place(self, tree: Any) -> Any:
        if self.ledger is not None:
            nb = tree_bytes(tree)
            self.ledger.record(self.tier, self.tensor_class, nb)
            self.ledger.record_capacity(self.tier, self.tensor_class, nb)
        return tiers.host_put(tree)

    def sharding(self, mesh, spec):
        return tiers.tier_sharding(mesh, spec, self.tier)

    def pick_tier(self, access_stats: dict | None = None) -> str:
        """Access-frequency placement: ``route_fraction`` (this bank's
        share of routed tokens) below ``cold_route_fraction`` -> cold."""
        if (access_stats is not None
                and access_stats.get("route_fraction", 1.0)
                < self.cold_route_fraction):
            return tiers.COLD
        return self.tier

    def bank_tiers(self, route_counts) -> list[str]:
        """Per-expert tier choice from observed routing counts (one
        count per expert): expert e's share of total routes drives
        :meth:`pick_tier`."""
        counts = [int(c) for c in route_counts]
        total = max(sum(counts), 1)
        return [self.pick_tier({"route_fraction": c / total})
                for c in counts]

    def rebalance(self, banks: dict, route_counts) -> list[str]:
        """Re-split the ledger's ``expert_weights`` residency between
        the home tier and cold from observed routing, charging the tier
        edge for every expert bank that moved since the last rebalance.

        The physical banks stay ONE stacked array per key (a per-expert
        physical split would retrace the routed gather); what moves is
        the hierarchy's *view* — residency lines and modeled transfer
        charges.  The gather reads the same array either way, so routed
        outputs are bit-identical by construction."""
        chosen = self.bank_tiers(route_counts)
        cold = {i for i, t in enumerate(chosen) if t == tiers.COLD}
        if self.ledger is not None:
            nb = tree_bytes({k: banks[k] for k in self.bank_keys
                             if k in banks})
            per = nb // max(self.num_experts, 1)
            prev = getattr(self, "_cold_experts", set())
            for _ in cold - prev:
                self.ledger.charge_transfer(self.tier, tiers.COLD, per)
            for _ in prev - cold:
                self.ledger.charge_transfer(tiers.COLD, self.tier, per)
            cold_b = per * len(cold)
            self.ledger.record(self.tier, self.tensor_class, nb - cold_b)
            self.ledger.record(tiers.COLD, self.tensor_class, cold_b)
            self._cold_cap = max(getattr(self, "_cold_cap", 0), cold_b)
            self.ledger.record_capacity(tiers.COLD, self.tensor_class,
                                        self._cold_cap)
        self._cold_experts = cold
        return chosen

    def resident_bytes(self, banks: dict, num_rows: int) -> int:
        """Local bytes the gather keeps resident: ``num_rows`` routed
        rows + 1 staging row per bank (the in-flight fetch)."""
        total = 0
        for k in self.bank_keys:
            bank = banks[k]
            row = tree_bytes(bank) // max(bank.shape[0], 1)
            total += (min(num_rows, bank.shape[0]) + 1) * row
        return total

    def gather(self, banks: dict, ids: jax.Array) -> dict:
        """Page in the routed expert rows: ``ids`` (N,) expert indices
        (duplicates fine — XLA gathers each row once per reference).
        Returns ``{key: (N, ...)}`` local-resident rows.  Residency is
        shape-derived, so it is recorded at trace time."""
        n = int(ids.shape[0])
        if self.ledger is not None:
            nb = self.resident_bytes(banks, n)
            self.ledger.record(tiers.LOCAL, self.tensor_class, nb)
            # gather staging is provisioned at its largest routed set
            cap = max(getattr(self, "_local_cap", 0), nb)
            self._local_cap = cap
            self.ledger.record_capacity(tiers.LOCAL, self.tensor_class, cap)
        # one row at a time: a gather cannot index a host-resident bank
        # (XLA computes no gather in host memory), a dynamic slice can,
        # and each slice crosses the tier edge on its own
        def row(i):
            return {k: tiers.page_in(jax.lax.dynamic_index_in_dim(
                banks[k], i, 0, keepdims=False)) for k in self.bank_keys}

        return jax.lax.map(row, ids)
