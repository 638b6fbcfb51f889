"""Memory-tier registry: the FengHuang hierarchy resolved per backend.

Maps the paper's multi-tier shared-memory hierarchy onto JAX memory
kinds, as an ORDERED hierarchy (fastest first):

* **local tier**  = ``memory_kind="device"`` (HBM),
* **remote tier** = the best host-side kind the backend exposes —
  ``pinned_host`` (host DRAM behind the DMA engine; the TAB-attached
  LPDDR6 pool in the paper's node).  TPU and CPU backends both expose
  ``device``, ``pinned_host`` and ``unpinned_host`` as distinct
  memories, so a CPU run places and moves bytes exactly as the chip
  does, and a missing host transfer fails on CPU as it would on TPU,
* **cold tier**   = the capacity backstop (the High-Bandwidth-Flash
  direction in Ma & Patterson): the next distinct host kind after the
  remote tier's (``unpinned_host``), or — on a backend exposing only
  one host kind — the SAME kind as remote.  Tiers are logical levels of
  the hierarchy, not memory kinds: the ledger, the swapper and the
  bandwidth model keep them distinct even when two share a kind.

A backend that exposes no host memory kind cannot back the remote tier,
and resolution raises instead of leaving the tier unbacked.

Each :class:`Tier` carries a *modeled* ``bandwidth_gbps`` /
``latency_us`` for its link into the hierarchy; tier-edge transfer time
(:meth:`TierRegistry.edge`) goes through the same
:func:`repro.memory.accounting.modeled_transfer_s` formula the Table-4.3
simulator's :class:`~repro.core.latency.LinkModel` uses, so ledger-
charged and simulated transfer costs stay one code path.

Resolution is cached **per backend** in a :class:`TierRegistry`,
invalidated by :func:`reset` (used by tests and by anything that swaps
the default backend mid-process).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.memory.accounting import modeled_transfer_s

# Canonical tier names used across policies, the ledger and BENCH JSON,
# in hierarchy order (fastest/smallest first).
LOCAL = "local"
REMOTE = "remote"
COLD = "cold"
HIERARCHY = (LOCAL, REMOTE, COLD)

LOCAL_KIND = "device"
REMOTE_KIND = "pinned_host"

# Host-side kinds that can back the FengHuang remote tier, best first.
_HOST_KINDS = ("pinned_host", "unpinned_host")

# Modeled per-tier link parameters (bandwidth_gbps, latency_us) — the
# bandwidth of each tier's link into the hierarchy and its access
# latency.  local ~ H200-class HBM; remote ~ the FengHuang TAB crossbar
# slice per GPU (§4.1, 4 TB/s); cold ~ High-Bandwidth-Flash (Ma &
# Patterson: HBM-adjacent bandwidth class, but a real latency gap).
# These are MODEL numbers charged by the ledger, not measurements.
DEFAULT_TIER_LINKS: dict[str, tuple[float, float]] = {
    LOCAL: (4800.0, 0.22),
    REMOTE: (4000.0, 2.0),
    COLD: (64.0, 50.0),
}

# Traced transfers name a memory space, not a kind: the two kinds the
# paging transforms move between inside jit.
_SPACES = {LOCAL_KIND: jax.memory.Space.Device,
           REMOTE_KIND: jax.memory.Space.Host}


def _link(name: str) -> tuple[float, float]:
    return DEFAULT_TIER_LINKS.get(name, DEFAULT_TIER_LINKS[REMOTE])


@dataclasses.dataclass(frozen=True)
class Tier:
    """One level of the hierarchy: a logical name bound to the memory
    kind that backs it on the current backend (None = unavailable),
    plus the modeled bandwidth/latency of its link into the hierarchy.

    Several tiers may share one memory kind (a backend with a single
    host kind backs remote and cold with it) — the logical level, not
    the kind, is what the ledger and policies reason about.
    """

    name: str
    kind: str | None
    bandwidth_gbps: float = 0.0
    latency_us: float = 0.0

    def __post_init__(self) -> None:
        if not self.bandwidth_gbps:
            bw, lat = _link(self.name)
            object.__setattr__(self, "bandwidth_gbps", bw)
            if not self.latency_us:
                object.__setattr__(self, "latency_us", lat)

    @property
    def available(self) -> bool:
        return self.kind is not None


@dataclasses.dataclass(frozen=True)
class TierEdge:
    """The modeled link between two tiers: bandwidth is the bottleneck
    of the two endpoints, latency crosses both interfaces."""

    src: str
    dst: str
    bandwidth_gbps: float
    latency_us: float

    def transfer_s(self, nbytes: int) -> float:
        """Modeled time to move ``nbytes`` across this edge (the same
        formula the simulator's LinkModel uses — one code path)."""
        return modeled_transfer_s(nbytes,
                                  bandwidth_gbps=self.bandwidth_gbps,
                                  latency_us=self.latency_us)


class TierRegistry:
    """Backend-scoped tier resolution.

    ``registry().local`` / ``.remote`` resolve lazily against the
    *current* default backend and are re-resolved after :func:`reset`
    or when the default backend changes — fixing the stale module-level
    ``lru_cache`` the old ``core.pager`` carried."""

    def __init__(self) -> None:
        self._tiers: dict[str, dict[str, Tier]] = {}

    def _backend(self) -> str:
        return jax.default_backend()

    def _resolve(self, backend: str) -> dict[str, Tier]:
        """Resolve the ORDERED hierarchy (local, remote, cold) against
        the backend's exposed memory kinds.  The cold tier falls back to
        the remote tier's host kind on a backend with one host kind —
        tiers stay logically distinct even when physically aliased.
        Raises ``RuntimeError`` when the backend has no device memory
        or no host memory kind: the remote tier would be unbacked, and
        every transfer into it a silent no-op."""
        kinds = frozenset(
            m.kind for m in jax.devices()[0].addressable_memories())
        remote = next((k for k in _HOST_KINDS if k in kinds), None)
        if LOCAL_KIND not in kinds or remote is None:
            raise RuntimeError(
                f"backend {backend!r} exposes memory kinds {sorted(kinds)}; "
                f"the tier hierarchy needs {LOCAL_KIND!r} and one of "
                f"{list(_HOST_KINDS)}")
        local = LOCAL_KIND
        # cold: the next distinct host kind after remote's, else remote's
        cold = next((k for k in _HOST_KINDS
                     if k in kinds and k != remote), remote)
        return {LOCAL: Tier(LOCAL, local), REMOTE: Tier(REMOTE, remote),
                COLD: Tier(COLD, cold)}

    def tiers(self) -> dict[str, Tier]:
        backend = self._backend()
        if backend not in self._tiers:
            self._tiers[backend] = self._resolve(backend)
        return self._tiers[backend]

    def hierarchy(self) -> tuple[Tier, ...]:
        """The resolved tiers in hierarchy order, fastest first."""
        return tuple(self.tiers().values())

    def tier(self, name: str) -> Tier:
        t = self.tiers().get(name)
        if t is None:
            raise KeyError(f"unknown tier {name!r}; hierarchy is "
                           f"{[x.name for x in self.hierarchy()]}")
        return t

    def edge(self, src: str, dst: str) -> TierEdge:
        """The modeled link between two tiers.  Unknown names fall back
        to the default link table, so ledger charging never throws on a
        custom tier label."""
        resolved = self.tiers()

        def params(name):
            t = resolved.get(name)
            if t is not None:
                return t.bandwidth_gbps, t.latency_us
            return _link(name)

        (sbw, slat), (dbw, dlat) = params(src), params(dst)
        return TierEdge(src=src, dst=dst,
                        bandwidth_gbps=min(sbw, dbw) or max(sbw, dbw),
                        latency_us=slat + dlat)

    @property
    def local(self) -> Tier:
        return self.tiers()[LOCAL]

    @property
    def remote(self) -> Tier:
        return self.tiers()[REMOTE]

    @property
    def cold(self) -> Tier:
        return self.tier(COLD)

    def reset(self) -> None:
        """Drop every cached resolution (tests; backend swaps)."""
        self._tiers.clear()


_REGISTRY = TierRegistry()


def registry() -> TierRegistry:
    return _REGISTRY


def reset() -> None:
    """Invalidate the process-wide tier registry."""
    _REGISTRY.reset()


def resolved_kind(tier: str) -> str | None:
    """The memory kind backing ``tier`` on this backend (None for a
    tier name outside the hierarchy)."""
    t = _REGISTRY.tiers().get(tier)
    return t.kind if t is not None else None


def resolved_local_kind() -> str | None:
    """The memory kind backing the local tier on this backend."""
    return _REGISTRY.local.kind


def resolved_remote_kind() -> str | None:
    """The memory kind backing the remote tier on this backend."""
    return _REGISTRY.remote.kind


def resolved_cold_kind() -> str | None:
    """The memory kind backing the cold tier on this backend."""
    return resolved_kind(COLD)


def supports_memory_spaces() -> bool:
    """True if the backend exposes a host memory kind the remote tier can
    live in (resolution raises otherwise, so this is True wherever it
    returns)."""
    return _REGISTRY.remote.available


# ---------------------------------------------------------------------------
# Fault injection: tier transfers as fallible, bounded-latency operations
# ---------------------------------------------------------------------------

class TierTransferError(RuntimeError):
    """A tier transfer failed (injected by a :class:`FaultPlan`, or a
    real backend failure surfaced through the retry wrapper)."""


@dataclasses.dataclass
class FaultPlan:
    """Deterministic (seeded) fault injection for tier transfers.

    Installed process-wide via :func:`install_fault_plan` /
    :func:`fault_plan`; every *eager* tier transfer (``host_put``, the
    PageSwapper's swap copies) consults the active plan before moving
    bytes.  Two injection styles compose:

    * counted — ``fail_first_n`` / ``spike_first_n`` hit the first N
      transfer attempts exactly (reproducible single-fault scenarios);
    * sampled — ``fail_rate`` / ``spike_rate`` draw per attempt from a
      ``numpy`` generator seeded with ``seed``, so a run with the same
      plan and the same transfer sequence injects the same faults.

    ``exhaust_at_block`` arms pool-exhaustion-mid-decode: the serving
    loop asks :meth:`take_pool_exhaustion` once per decode block and, at
    the armed block, steals every free page for ``exhaust_blocks``
    blocks — forcing a real mid-decode ``MemoryError`` and exercising
    the emergency-preemption recovery path.

    ``crash_prefill_at_chunk`` / ``crash_adopt_at_block`` arm
    **engine-crash injection** for disaggregated serving: the prefill
    engine asks :meth:`take_prefill_crash` before every chunk dispatch
    and, at the armed chunk, dies mid-prompt (its in-flight prefills
    and un-adopted handoffs become orphans whose pool pages only the
    server-side lease watchdog can reclaim); the decode engine asks
    :meth:`take_adopt_crash` at every handoff adoption and, at the
    armed block, drops the handoff mid-adoption — the staged pages
    survive in the registry until the handoff's lease expires.
    """

    seed: int = 0
    fail_first_n: int = 0
    fail_rate: float = 0.0
    spike_first_n: int = 0
    spike_rate: float = 0.0
    spike_s: float = 0.05
    exhaust_at_block: int | None = None
    exhaust_blocks: int = 2
    crash_prefill_at_chunk: int | None = None
    crash_adopt_at_block: int | None = None

    def __post_init__(self) -> None:
        self._rng = np.random.default_rng(self.seed)
        self.transfers = 0       # attempts observed
        self.failures = 0        # attempts failed
        self.spikes = 0          # attempts delayed
        self._exhaust_armed = self.exhaust_at_block is not None
        self._prefill_chunks = 0
        self._prefill_crash_armed = self.crash_prefill_at_chunk is not None
        self._adopt_crash_armed = self.crash_adopt_at_block is not None

    def before_transfer(self, what: str, nbytes: int = 0) -> None:
        """Called by the transfer wrapper before each attempt; sleeps for
        an injected latency spike, raises for an injected failure."""
        idx = self.transfers
        self.transfers += 1
        spike = idx < self.spike_first_n or (
            self.spike_rate > 0.0 and self._rng.random() < self.spike_rate)
        if spike:
            self.spikes += 1
            time.sleep(self.spike_s)
        fail = idx < self.fail_first_n or (
            self.fail_rate > 0.0 and self._rng.random() < self.fail_rate)
        if fail:
            self.failures += 1
            raise TierTransferError(
                f"injected transfer failure #{self.failures} "
                f"({what}, attempt {idx}, {nbytes} bytes)")

    def take_pool_exhaustion(self, block: int) -> bool:
        """True exactly once, at the armed decode block (the caller then
        steals the pool's free pages and releases them after
        ``exhaust_blocks`` blocks)."""
        if self._exhaust_armed and block >= self.exhaust_at_block:
            self._exhaust_armed = False
            return True
        return False

    def take_prefill_crash(self) -> bool:
        """Counts prefill chunk dispatches; True exactly once, when the
        armed chunk is about to go out (the prefill engine then dies
        mid-prompt, orphaning its in-flight work)."""
        self._prefill_chunks += 1
        if (self._prefill_crash_armed
                and self._prefill_chunks >= self.crash_prefill_at_chunk):
            self._prefill_crash_armed = False
            return True
        return False

    def take_adopt_crash(self, block: int) -> bool:
        """True exactly once, at the armed decode block's handoff
        adoption (the decode engine then drops the handoff mid-adoption
        without rebinding its pages)."""
        if self._adopt_crash_armed and block >= self.crash_adopt_at_block:
            self._adopt_crash_armed = False
            return True
        return False


_FAULT_PLAN: FaultPlan | None = None


def install_fault_plan(plan: FaultPlan | None) -> FaultPlan | None:
    """Install (or clear, with None) the process-wide fault plan;
    returns the previously installed plan."""
    global _FAULT_PLAN
    prev, _FAULT_PLAN = _FAULT_PLAN, plan
    return prev


def active_fault_plan() -> FaultPlan | None:
    return _FAULT_PLAN


@contextlib.contextmanager
def fault_plan(plan: FaultPlan):
    """Scoped fault injection (chaos tests)."""
    prev = install_fault_plan(plan)
    try:
        yield plan
    finally:
        install_fault_plan(prev)


def check_transfer(what: str, nbytes: int = 0) -> None:
    """Fault-injection checkpoint for one eager tier-transfer attempt."""
    if _FAULT_PLAN is not None:
        _FAULT_PLAN.before_transfer(what, nbytes)


def transfer_with_retry(fn: Callable[[], Any], *, what: str,
                        nbytes: int = 0, retries: int = 3,
                        backoff_s: float = 0.001,
                        timeout_s: float | None = None,
                        monitor=None) -> Any:
    """Run one tier transfer with retry + exponential backoff + timeout.

    ``fn`` performs the actual bytes movement and may raise
    :class:`TierTransferError` (injected or real).  Each attempt's
    duration is reported to ``monitor`` (a
    :class:`repro.runtime.ft.StragglerMonitor`) so slow-but-successful
    transfers are flagged rather than silently absorbed.  An attempt
    exceeding ``timeout_s`` violates the bounded-latency contract and is
    treated as failed (its result is discarded and the transfer
    retried).  After ``retries`` retries the error propagates as
    :class:`TierTransferError` — the caller's graceful-degradation
    policy takes over from there."""
    delay = backoff_s
    last: Exception | None = None
    for attempt in range(retries + 1):
        t0 = time.monotonic()
        try:
            check_transfer(what, nbytes)
            out = fn()
        except TierTransferError as e:
            last = e
        else:
            dt = time.monotonic() - t0
            if monitor is not None:
                monitor.observe(dt)
            if timeout_s is None or dt <= timeout_s:
                return out
            last = TierTransferError(
                f"{what} attempt {attempt} took {dt:.3f}s "
                f"(> timeout {timeout_s:.3f}s)")
        if attempt < retries:
            time.sleep(delay)
            delay *= 2
    raise TierTransferError(
        f"{what} failed after {retries + 1} attempts: {last}") from last


# ---------------------------------------------------------------------------
# Placement primitives
# ---------------------------------------------------------------------------

def tier_sharding(mesh, pspec: P, tier: str) -> NamedSharding:
    """NamedSharding placing data in ``tier`` (any hierarchy level) with
    the memory kind the *current backend* actually exposes — resolved
    through the registry, never hardcoded.  A tier name outside the
    hierarchy gets the backend's default memory."""
    kind = _REGISTRY.tiers().get(tier, Tier(tier, None)).kind
    return NamedSharding(mesh, pspec, memory_kind=kind)


def remote_sharding(mesh, pspec: P) -> NamedSharding:
    """NamedSharding in the FengHuang remote tier."""
    return tier_sharding(mesh, pspec, REMOTE)


def local_sharding(mesh, pspec: P) -> NamedSharding:
    return tier_sharding(mesh, pspec, LOCAL)


def to_remote(tree: Any, mesh, pspec_tree: Any) -> Any:
    """Move a pytree of arrays into the remote tier (sharded)."""
    return jax.tree.map(
        lambda x, ps: jax.device_put(x, remote_sharding(mesh, ps)),
        tree, pspec_tree)


def _put_kind(x: jax.Array, kind: str) -> jax.Array:
    """Move ``x`` into memory ``kind``: a traced transfer inside jit
    (device <-> pinned host only), an eager re-placement otherwise."""
    if isinstance(x, jax.core.Tracer):
        if kind not in _SPACES:
            raise ValueError(f"no traced transfer into memory kind {kind!r}")
        return jax.device_put(x, _SPACES[kind])
    return jax.device_put(x, x.sharding.with_memory_kind(kind))


def page_in(tree: Any) -> Any:
    """Fetch a pytree from the remote tier into local (device) memory.

    Traceable: inside jit this lowers to an async H2D copy that XLA
    schedules concurrently with unrelated compute (the paging stream).
    """
    return jax.tree.map(lambda x: _put_kind(x, resolved_local_kind()), tree)


def page_out(tree: Any) -> Any:
    """Evict a pytree to the remote tier (write-back)."""
    return jax.tree.map(lambda x: _put_kind(x, resolved_remote_kind()), tree)


def eager_to_tier(tree: Any, tier: str, *, what: str | None = None) -> Any:
    """Eagerly place a pytree in ``tier`` (single-device helper for
    examples/tests; sharded placement goes through :func:`tier_sharding`
    / :func:`to_remote`).

    As an *eager* tier transfer it is a fault-injection checkpoint: an
    installed :class:`FaultPlan` may delay or fail it, and callers with
    a degradation policy (``MemoryOrchestrator.place`` /
    ``place_kv_pool``) catch :class:`TierTransferError`, fall back to
    local residency and record the degradation in
    ``MemoryOrchestrator.degraded``."""
    leaves = [x for x in jax.tree.leaves(tree) if hasattr(x, "nbytes")]
    check_transfer(what or f"eager_to_{tier}",
                   sum(x.nbytes for x in leaves))
    kind = resolved_kind(tier)
    return jax.tree.map(lambda x: _put_kind(jnp.asarray(x), kind), tree)


def eager_to_remote(tree: Any) -> Any:
    """Eagerly place a pytree in the remote tier (fault-checkpointed)."""
    return eager_to_tier(tree, REMOTE, what="host_put")


def host_put(tree: Any) -> Any:
    """Historic name for :func:`eager_to_remote` (kept: it is the eager
    placement primitive every policy's ``place`` rides)."""
    return eager_to_remote(tree)
