"""PageSwapper: batched KV-page transfers between the device block pool
and the remote tier — the mechanism behind page-granular preemption.

Swapping a victim sequence out gathers its live pages from the stacked
device pools in ONE batched take per pool, moves the bytes to the remote
tier (host-resident stash on every backend; on CPU local == remote and
the copy degenerates to a host copy with identical semantics), and hands
back an opaque :class:`SwapHandle`.  Swapping back in scatters the
stashed pages into freshly allocated page ids with one donated dispatch
per pool pair — bucketed to a power-of-two page count so executables
stay O(log pool) over a server's lifetime.

Every transfer is a *fallible, bounded-latency* operation: it runs
through :func:`repro.memory.tiers.transfer_with_retry` (fault-injection
checkpoint, retry with exponential backoff, timeout) and reports its
duration to an optional :class:`repro.runtime.ft.StragglerMonitor` so
slow tier transfers are flagged.  Stashed bytes are ledger-accounted
per tier under the ``kv_swap`` tensor class, and every stash movement
(swap-out, swap-in, :meth:`PageSwapper.park` to the cold tier,
:meth:`PageSwapper.promote` back up) charges the ledger's tier-edge
transfer model.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.memory import tiers
from repro.memory.accounting import MemoryLedger


@dataclasses.dataclass
class SwapHandle:
    """Remote-tier stash of one sequence's KV pages (host arrays).

    Quantized pools stash their per-slot dequant scales alongside the
    values (``k_scale``/``v_scale``, (L, n, Hkv, page)) so a restore is
    byte-for-byte the pages that were swapped out — the quantized
    preemption bit-identity contract.

    A DEFERRED stash (``swap_out(..., defer=True)``) holds the staged
    copy as device arrays behind ``_pull`` until someone actually reads
    the bytes (snapshot serialization, swap-in) — callers touching
    ``k``/``v`` directly must :meth:`materialize` first.  Accounting and
    fault injection are NOT deferred: the stash's bytes joined the
    remote-tier ledger line and its transfer slot fired when it was
    created.

    ``tier`` names the hierarchy level the stash currently occupies
    (``remote`` at creation; ``cold`` after
    :meth:`PageSwapper.park` demotes a long-idle stash).  Moving a
    stash between tiers never touches the bytes — only accounting and
    the modeled transfer cost move — so a cold-parked stash restores
    bit-identically."""

    page_count: int
    k: np.ndarray | None     # (L, n, Hkv, page, hd)
    v: np.ndarray | None
    nbytes: int
    k_scale: np.ndarray | None = None
    v_scale: np.ndarray | None = None
    _pull: object = None     # () -> [k, v(, k_scale, v_scale)] host pull
    tier: str = tiers.REMOTE

    def materialize(self) -> "SwapHandle":
        """Resolve a deferred stash to host arrays (idempotent)."""
        if self._pull is not None:
            host = self._pull()
            self.k, self.v = host[0], host[1]
            if len(host) > 2:
                self.k_scale, self.v_scale = host[2], host[3]
            self._pull = None
        return self


def _bucket_pages(n: int, quantum: int = 4) -> int:
    b = quantum
    while b < n:
        b *= 2
    return b


class PageSwapper:
    """Batched swap-out/swap-in of block-pool KV pages.

    One instance per server; ``retries``/``backoff_s``/``timeout_s``
    parameterize the transfer contract and ``monitor`` (a
    ``StragglerMonitor``) flags slow transfers.  The swap-in scatter is
    jitted with the pool donated, so restores splice into the live cache
    without copying it.
    """

    def __init__(self, *, ledger: MemoryLedger | None = None,
                 tier: str = tiers.REMOTE, retries: int = 3,
                 backoff_s: float = 0.001, timeout_s: float | None = None,
                 monitor=None, tensor_class: str = "kv_swap"):
        # "kv_swap" for preemption stashes; "kv_handoff" when the same
        # gather/stash machinery stages prefill->decode page handoffs
        # (see repro.runtime.prefill) — separate ledger lines so the two
        # uses of the remote tier stay independently auditable
        self.tensor_class = tensor_class
        self.ledger = ledger
        self.tier = tier
        self.retries = retries
        self.backoff_s = backoff_s
        self.timeout_s = timeout_s
        self.monitor = monitor
        self.swap_outs = 0
        self.swap_ins = 0
        self.parks = 0               # stashes demoted to a colder tier
        self.promotes = 0            # stashes promoted back up
        self.retry_attempts = 0      # failed attempts that were retried
        self.live_handles = 0        # stashes created and not yet released
        # per-tier stash accounting: a swapper's stashes may sit in
        # several hierarchy levels at once (fresh stashes remote,
        # long-idle ones cold-parked)
        self._stash_bytes: dict[str, int] = {}
        self._stash_hwm: dict[str, int] = {}
        self._scatter = jax.jit(self._scatter_fn, donate_argnums=(0,))
        self._gather = jax.jit(self._gather_fn)

    # ----- ledger ------------------------------------------------------------
    def _record(self, tier: str | None = None) -> None:
        if self.ledger is None:
            return
        for t in ([tier] if tier is not None else self._stash_bytes):
            b = self._stash_bytes.get(t, 0)
            self.ledger.record(t, self.tensor_class, b)
            # the stash arena grows on demand: its provisioned capacity
            # is the largest footprint it ever held, keeping the tier's
            # hwm <= capacity invariant auditable
            hwm = max(self._stash_hwm.get(t, 0), b)
            self._stash_hwm[t] = hwm
            self.ledger.record_capacity(t, self.tensor_class, hwm)

    def _account(self, tier: str, delta: int) -> None:
        self._stash_bytes[tier] = self._stash_bytes.get(tier, 0) + delta
        self._record(tier)

    def _transfer(self, fn, *, what: str, nbytes: int):
        before = (tiers.active_fault_plan().failures
                  if tiers.active_fault_plan() else 0)
        try:
            return tiers.transfer_with_retry(
                fn, what=what, nbytes=nbytes, retries=self.retries,
                backoff_s=self.backoff_s, timeout_s=self.timeout_s,
                monitor=self.monitor)
        finally:
            plan = tiers.active_fault_plan()
            if plan is not None:
                self.retry_attempts += plan.failures - before

    # ----- swap out ----------------------------------------------------------
    def _gather_fn(self, cache: dict, pids: jax.Array) -> list[jax.Array]:
        """One fused dispatch for the whole stash gather (the un-jitted
        per-pool ``jnp.take`` chain costs a device round trip per pool,
        which dominates small swaps — e.g. every prefill handoff)."""
        from repro.kernels.paged_attention.ref import gatherable_view
        cache = tiers.page_in(cache)    # no gather in host memory

        def take(pool):
            # fp8 pools gather as a uint8 bit-view and bitcast back —
            # bit-preserving, and it keeps the stash gather off XLA:CPU's
            # ~8x-slower fp8 gather kernel
            g = jnp.take(gatherable_view(pool), pids, axis=1)
            if g.dtype != pool.dtype:
                g = jax.lax.bitcast_convert_type(g, pool.dtype)
            return g

        out = [take(cache["k_pages"]), take(cache["v_pages"])]
        if "k_scale" in cache:
            out += [jnp.take(cache["k_scale"], pids, axis=1),
                    jnp.take(cache["v_scale"], pids, axis=1)]
        return out

    def swap_out(self, cache: dict, page_ids: list[int],
                 defer: bool = False, tier: str | None = None) -> SwapHandle:
        """Gather ``page_ids`` from the stacked pools and stash them in
        ``tier`` (the swapper's home tier — normally remote — when not
        given; ``tiers.COLD`` stashes a deep-preemption victim directly
        in the cold tier so the remote tier never holds it); raises
        :class:`tiers.TierTransferError` after the retry budget is
        exhausted (the caller's degradation policy — shed the victim —
        takes over).

        ``defer=True`` keeps the staged copy on device and postpones the
        host byte movement until the stash is read (a handoff adopted
        in-process releases it unread, so the hot path never pays the
        pull).  The transfer SLOT is not deferred: seeded fault/latency
        injection, the straggler monitor and the retry budget all fire
        here, at the same schedule position as an eager swap."""
        tier = self.tier if tier is None else tier
        # bucket the gather width so the jitted executable is reused
        # across nearby page counts (pad with the null page, slice the
        # true count back out on the host)
        n = len(page_ids)
        b = _bucket_pages(n)
        pids = jnp.asarray(list(page_ids) + [0] * (b - n), jnp.int32)
        grab = self._gather(cache, pids)
        quant = "k_scale" in cache
        # per-array bytes (true pages only): a quantized stash mixes
        # int8/fp8 values with bf16 scales, so a single shared itemsize
        # would misaccount
        nbytes = sum(a.size // b * n * a.dtype.itemsize for a in grab)

        def pull():
            return [np.asarray(a[:, :n]) for a in jax.device_get(grab)]

        if defer:
            self._transfer(lambda: None, what="kv_swap_out", nbytes=nbytes)
            handle = SwapHandle(page_count=n, k=None, v=None,
                                nbytes=nbytes, _pull=pull, tier=tier)
        else:
            host = self._transfer(pull, what="kv_swap_out", nbytes=nbytes)
            handle = SwapHandle(page_count=n, k=host[0], v=host[1],
                                nbytes=nbytes,
                                k_scale=host[2] if quant else None,
                                v_scale=host[3] if quant else None,
                                tier=tier)
        self.swap_outs += 1
        self.live_handles += 1
        self._account(tier, nbytes)
        if self.ledger is not None:
            self.ledger.charge_transfer(tiers.LOCAL, tier, nbytes)
        return handle

    # ----- swap in -----------------------------------------------------------
    def _scatter_fn(self, cache: dict, pids: jax.Array, k: jax.Array,
                    v: jax.Array, k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None) -> dict:
        from repro.kernels.paged_attention.ops import (STACKED_POOL_SPEC,
                                                       STACKED_SCALE_SPEC)
        from repro.runtime.sharding import maybe_constraint
        cache = tiers.page_in(cache)    # no scatter in host memory
        k = maybe_constraint(k, STACKED_POOL_SPEC)
        v = maybe_constraint(v, STACKED_POOL_SPEC)
        out = dict(cache)
        out["k_pages"] = cache["k_pages"].at[:, pids].set(
            k.astype(cache["k_pages"].dtype))
        out["v_pages"] = cache["v_pages"].at[:, pids].set(
            v.astype(cache["v_pages"].dtype))
        if k_scale is not None:
            out["k_scale"] = cache["k_scale"].at[:, pids].set(
                maybe_constraint(k_scale, STACKED_SCALE_SPEC))
            out["v_scale"] = cache["v_scale"].at[:, pids].set(
                maybe_constraint(v_scale, STACKED_SCALE_SPEC))
        return out

    def swap_in(self, cache: dict, page_ids: list[int],
                handle: SwapHandle) -> dict:
        """Scatter a stash back into freshly allocated ``page_ids`` (same
        order as the swap-out) and release the stash.  Returns the new
        cache; the old one is donated.  Padding entries (bucketed width)
        point at the null page 0, which no live table ever reads."""
        if len(page_ids) != handle.page_count:
            raise ValueError(f"swap_in got {len(page_ids)} pages for a "
                             f"{handle.page_count}-page stash")
        handle.materialize()
        n = handle.page_count
        cap = _bucket_pages(max(n, 1))
        pids = np.zeros(cap, np.int32)
        pids[:n] = page_ids
        pad = ((0, 0), (0, cap - n)) + ((0, 0),) * (handle.k.ndim - 2)
        k = np.pad(handle.k, pad)
        v = np.pad(handle.v, pad)
        scales = ()
        if handle.k_scale is not None:
            spad = pad[:-1]
            scales = (jnp.asarray(np.pad(handle.k_scale, spad)),
                      jnp.asarray(np.pad(handle.v_scale, spad)))

        def push():
            return self._scatter(cache, jnp.asarray(pids), jnp.asarray(k),
                                 jnp.asarray(v), *scales)

        new_cache = self._transfer(push, what="kv_swap_in",
                                   nbytes=handle.nbytes)
        self.swap_ins += 1
        if self.ledger is not None:
            self.ledger.charge_transfer(handle.tier, tiers.LOCAL,
                                        handle.nbytes)
        self.release(handle)
        return new_cache

    # ----- tier moves ---------------------------------------------------------
    def _move(self, handle: SwapHandle, tier: str, *, what: str) -> SwapHandle:
        """Move a stash between hierarchy levels.  The bytes are never
        touched — a park/promote is a fault-injection checkpoint, a
        per-tier accounting move and a modeled edge charge — so a
        round-tripped stash restores bit-identically by construction.
        Deferred stashes materialize first: cold-parking is the moment
        the bytes must actually leave the device."""
        if handle.tier == tier or not handle.nbytes:
            return handle
        handle.materialize()
        src = handle.tier
        self._transfer(lambda: None, what=what, nbytes=handle.nbytes)
        self._account(src, -handle.nbytes)
        self._account(tier, handle.nbytes)
        if self.ledger is not None:
            self.ledger.charge_transfer(src, tier, handle.nbytes)
        handle.tier = tier
        return handle

    def park(self, handle: SwapHandle, tier: str = tiers.COLD) -> SwapHandle:
        """Demote a stash to a colder tier (default ``cold``) — the
        long-idle-preemption path.  Fallible like any transfer: a
        :class:`tiers.TierTransferError` leaves the stash where it was."""
        h = self._move(handle, tier, what="kv_cold_park")
        self.parks += 1
        return h

    def promote(self, handle: SwapHandle,
                tier: str = tiers.REMOTE) -> SwapHandle:
        """Promote a stash back up the hierarchy (default ``remote`` —
        the promote-through-remote step a cold-parked victim pays before
        its swap-in; resume then charges remote->local as usual)."""
        h = self._move(handle, tier, what="kv_cold_promote")
        self.promotes += 1
        return h

    def adopt(self, handle: SwapHandle) -> None:
        """Account for a stash produced elsewhere (snapshot restore): the
        bytes join this swapper's ledger line for the tier the handle
        says it lives in, as if it had swapped them out itself."""
        self._account(handle.tier, handle.nbytes)
        self.live_handles += 1

    def release(self, handle: SwapHandle) -> None:
        """Drop a stash without restoring it (victim shed / expired
        deadline or lease / restore into a snapshot).  Idempotent: a
        double release — e.g. the lease watchdog racing a snapshot —
        is accounting-neutral."""
        if handle.nbytes:
            self._account(handle.tier, -handle.nbytes)
            handle.nbytes = 0
            self.live_handles -= 1

    @property
    def outstanding_bytes(self) -> int:
        """Stash bytes currently parked anywhere in the hierarchy — the
        leak gauge the chaos harness drives to zero after every
        reclamation (ledger drift zero <=> this is zero after a
        drain)."""
        return sum(self._stash_bytes.values())
