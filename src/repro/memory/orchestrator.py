"""MemoryOrchestrator: tensor classes -> residency policies, plus the
paged execution transforms they ride.

The *Tensor Prefetcher* becomes :func:`paged_scan`: a scan over stacked
per-layer weights whose carry holds a **double buffer** — iteration *i*
computes layer *i* from the already-fetched buffer while the fetch of
layer *i+1* is issued *before* the compute, so XLA's async
copy-start/copy-done pair (the "paging stream") overlaps the transfer
with layer *i*'s compute.  Peak device residency is 2 layers of weights
+ activations, which is the paper's Table 4.3 result (10–20 GB instead
of 144 GB).

Everything degrades gracefully: with ``enabled=False`` (or on backends
without host memory spaces) the transforms are plain ``lax.scan``s over
device-resident weights, so models are paging-agnostic.

:class:`MemoryOrchestrator` is the subsystem's front door:
``MemoryOrchestrator.plan(model_config)`` resolves the policy matrix
from the config's :class:`~repro.memory.policies.PagerConfig`, and the
instance then owns placement (``place_layer_weights`` /
``place_kv_pool`` / ``block_pool``), the layer scans (with the expert
banks automatically pinned out of the prefetch window when expert
paging is on), the donation contract, and the shared ledger.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.memory import tiers
from repro.memory.accounting import (MemoryLedger, paged_window_bytes,
                                     tree_bytes)
from repro.memory.policies import (BlockPoolResidency, DoubleBufferPrefetch,
                                   OffloadBetweenSteps, PagerConfig, PinLocal,
                                   TopKExpertPrefetch)


def donating_jit(fn: Callable, *, donate_argnums: tuple[int, ...] = (),
                 config: PagerConfig | None = None, **jit_kwargs) -> Callable:
    """``jax.jit`` with the FengHuang donation contract.

    The serving hot path hands its KV cache and decode state to every
    dispatch and never touches the old buffers again — exactly the
    "consumed double buffer" the pager's eviction policy describes.
    Donating them lets XLA alias input and output so the cache is updated
    in place instead of copied once per dispatch.  ``config.donate_evicted
    = False`` turns the aliasing off (debug mode: old buffers stay live).
    Under ``config.offload_kv`` nothing is donated: the cache arrives in
    host memory, which cannot hold the step's device-resident result.
    """
    if config is not None and (not config.donate_evicted
                               or config.offload_kv):
        donate_argnums = ()
    return jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)


def _index_layer(stacked: Any, i) -> Any:
    """Slice layer ``i`` out of a stacked (L, ...) pytree (stays in its
    current memory space)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        stacked)


def _page_in_filtered(layer: Any, fetch_filter: Callable | None) -> Any:
    """page_in the layer, leaving leaves the filter rejects at rest
    (expert banks under TopKExpertPrefetch stay remote — their rows are
    gathered on demand inside the layer body instead)."""
    if fetch_filter is None:
        return tiers.page_in(layer)
    return jax.tree_util.tree_map_with_path(
        lambda p, x: (tiers.page_in(x)
                      if fetch_filter(jax.tree_util.keystr(p)) else x),
        layer)


def _vectors_in(stacked: Any, fetch_filter: Callable | None) -> Any:
    """Page in, whole, the stacked leaves that hold one vector per layer
    (norm scales, biases): on TPU one layer's slice of a host-resident
    (L, N) array is a strided DMA whose stride the chip rejects (not a
    multiple of the DMA granule), while a slice of an (L, a, b) matrix
    stack is whole tiles.  The vectors are a few hundred KB; matrices
    still stream one layer at a time."""
    def pick(path, x):
        if x.ndim > 2 or (fetch_filter is not None
                          and not fetch_filter(jax.tree_util.keystr(path))):
            return x
        return tiers.page_in(x)
    return jax.tree_util.tree_map_with_path(pick, stacked)


def paged_scan(
    body: Callable[..., tuple[Any, Any]],
    carry: Any,
    stacked_weights: Any,
    xs: Any = None,
    *,
    config: PagerConfig,
    length: int | None = None,
    unroll: int = 1,
    page_xs: bool = False,
    fetch_filter: Callable[[str], bool] | None = None,
) -> tuple[Any, Any]:
    """FengHuang-paged scan over layers.

    ``body(carry, layer_weights[, x]) -> (carry, out)`` — layer weights
    arrive in the local tier.  With paging enabled, ``stacked_weights`` is
    expected to live in the remote tier; the double-buffered carry implements
    the lookahead-1 Tensor Prefetcher.  Differentiable (the transfers are
    linear), so the same transform serves training.

    ``xs`` is an optional extra per-layer input (e.g. the KV-cache slice for
    this layer).  With ``page_xs=True`` it is paged in alongside the weights
    (FengHuang KV paging); the stacked outputs come back in device
    memory.  Writing them back to the remote tier is the owner's eager
    placement between dispatches (``MemoryOrchestrator.rest_kv_pool``):
    a jitted program's outputs live in device memory.
    ``fetch_filter(leaf_path) -> bool`` excludes weight leaves from the
    prefetch window (False = leaf stays at rest).
    """
    if length is None:
        length = jax.tree.leaves(stacked_weights)[0].shape[0]

    if not config.enabled:
        if fetch_filter is None:
            if xs is None:
                return jax.lax.scan(body, carry, stacked_weights,
                                    unroll=unroll)
            return jax.lax.scan(lambda c, wx: body(c, wx[0], wx[1]), carry,
                                (stacked_weights, xs), unroll=unroll)

        # at-rest leaves (expert banks) must not stream through the scan
        # xs — index the layer inside the body so they stay in their tier
        # and only the rows the body gathers cross it
        def step(c, i):
            w = _index_layer(stacked_weights, i)
            if xs is None:
                return body(c, w)
            return body(c, w, _index_layer(xs, i))

        return jax.lax.scan(step, carry, jnp.arange(length), unroll=unroll)

    stacked_weights = _vectors_in(stacked_weights, fetch_filter)

    def fetch(i):
        return _page_in_filtered(_index_layer(stacked_weights, i),
                                 fetch_filter)

    last = length - 1
    w0 = fetch(0)

    def step(state, i):
        inner_carry, w_cur = state
        # Issue the prefetch of layer i+1 BEFORE the compute of layer i so
        # the copy-start precedes the matmuls in program order; XLA overlaps.
        w_next = fetch(jnp.minimum(i + 1, last))
        if xs is None:
            inner_carry, out = body(inner_carry, w_cur)
        else:
            x = _index_layer(xs, i)
            if page_xs:
                x = tiers.page_in(x)
            inner_carry, out = body(inner_carry, w_cur, x)
        return (inner_carry, w_next), out

    (carry, _), outs = jax.lax.scan(step, (carry, w0), jnp.arange(length),
                                    unroll=unroll)
    return carry, outs


def paged_scan_cache(
    body: Callable[..., tuple[Any, Any]],
    carry: Any,
    stacked_weights: Any,
    cache: Any,
    *,
    config: PagerConfig,
    length: int | None = None,
    fetch_filter: Callable[[str], bool] | None = None,
) -> tuple[Any, Any]:
    """Layer scan with the (stacked) cache threaded through the CARRY.

    ``body(carry, layer_weights, cache_layer) -> (carry, new_cache_layer)``.

    Unlike passing the cache as scan xs/ys — which makes XLA materialize a
    second full-size stacked buffer and copy the untouched layers every
    iteration — the carried buffer is updated in place with a
    dynamic-update-slice (while-loop state aliases input/output), so
    per-layer traffic is just that layer's slice.  With
    ``config.offload_kv`` the cache arrives from the FengHuang remote
    tier: it is paged in once, because the loop-carried buffer must live
    in device memory to take each layer's in-place update, and it is
    returned in device memory.  The owner writes it back to the remote
    tier between dispatches (``MemoryOrchestrator.rest_kv_pool``).
    """
    if length is None:
        length = jax.tree.leaves(stacked_weights)[0].shape[0]
    last = length - 1

    if config.enabled:
        stacked_weights = _vectors_in(stacked_weights, fetch_filter)

    def fetch(i):
        w = _index_layer(stacked_weights, i)
        return (_page_in_filtered(w, fetch_filter) if config.enabled else w)

    def update(buf, i, new_layer):
        return jax.tree.map(
            lambda b, u: jax.lax.dynamic_update_index_in_dim(
                b, u.astype(b.dtype), i, 0),
            buf, new_layer)

    if not config.enabled:
        def step(state, i):
            inner, cache_buf = state
            cl = _index_layer(cache_buf, i)
            inner, new_cl = body(inner, fetch(i), cl)
            return (inner, update(cache_buf, i, new_cl)), None

        (carry, cache), _ = jax.lax.scan(step, (carry, cache),
                                         jnp.arange(length))
        return carry, cache

    w0 = fetch(0)
    if config.offload_kv:
        cache = tiers.page_in(cache)

    def step(state, i):
        inner, cache_buf, w_cur = state
        w_next = fetch(jnp.minimum(i + 1, last))    # lookahead-1 prefetch
        cl = _index_layer(cache_buf, i)
        inner, new_cl = body(inner, w_cur, cl)
        return (inner, update(cache_buf, i, new_cl), w_next), None

    (carry, cache, _), _ = jax.lax.scan(step, (carry, cache, w0),
                                        jnp.arange(length))
    return carry, cache


def paged_map(fn: Callable[[Any], Any], stacked: Any, *,
              config: PagerConfig) -> Any:
    """Apply ``fn`` per layer with paging (utility for cache init etc.)."""
    def body(carry, w):
        return carry, fn(w)
    _, outs = paged_scan(body, (), stacked, config=config)
    return outs


class MemoryOrchestrator:
    """Binds tensor classes to residency policies for one model/server.

    Tensor classes: ``layer_weights`` (stacked per-layer params),
    ``kv_pool`` (dense slab or block pool), ``expert_weights`` (MoE
    banks).  ``plan`` resolves the policy matrix from a
    :class:`PagerConfig`; everything downstream — placement, layer
    scans, donation, block-pool bookkeeping, accounting — goes through
    the instance, so the server, benchmarks and examples never hand-wire
    pager calls.
    """

    def __init__(self, config: PagerConfig,
                 policies: dict[str, Any] | None = None,
                 ledger: MemoryLedger | None = None):
        self.config = config
        self.ledger = ledger if ledger is not None else MemoryLedger()
        self.policies = dict(policies or {})
        self.policies.setdefault("layer_weights", PinLocal())
        self.policies.setdefault("kv_pool", PinLocal())
        self.mesh = None          # bound by bind_mesh (sharded serving)
        self.model_shards = 1
        # tensor class -> reason, recorded when an unrecoverable tier
        # fault forced a documented degradation (e.g. remote KV offload
        # falling back to local residency)
        self.degraded: dict[str, str] = {}

    # ----- planning ---------------------------------------------------------
    @classmethod
    def plan(cls, model_config: Any = None,
             pager_config: PagerConfig | None = None,
             ledger: MemoryLedger | None = None) -> "MemoryOrchestrator":
        """The one entry point: resolve the policy matrix.

        ``model_config`` is a :class:`repro.models.base.ModelConfig` (its
        ``pager`` policy supplies the knobs unless ``pager_config``
        overrides) or None for a bare default orchestrator.
        """
        if pager_config is None:
            pp = getattr(model_config, "pager", None)
            pager_config = PagerConfig(
                enabled=getattr(pp, "enabled", False),
                lookahead=getattr(pp, "lookahead", 1),
                offload_kv=getattr(pp, "offload_kv", False),
                page_experts=getattr(pp, "page_experts", False))
        ledger = ledger if ledger is not None else MemoryLedger()
        policies: dict[str, Any] = {}
        policies["layer_weights"] = (
            DoubleBufferPrefetch(lookahead=pager_config.lookahead)
            if pager_config.enabled else PinLocal())
        policies["kv_pool"] = (
            OffloadBetweenSteps()
            if pager_config.enabled and pager_config.offload_kv
            else PinLocal())
        num_experts = getattr(model_config, "num_experts", 0)
        if pager_config.page_experts and num_experts:
            policies["expert_weights"] = TopKExpertPrefetch(
                num_experts=num_experts,
                top_k=getattr(model_config, "top_k", 1),
                ledger=ledger)
        return cls(pager_config, policies, ledger)

    # ----- mesh awareness ---------------------------------------------------
    def bind_mesh(self, mesh) -> "MemoryOrchestrator":
        """Make the orchestrator mesh-aware: residency policies then emit
        NamedShardings against ``mesh`` (with each policy's tier resolved
        to the backend's memory kind) and the ledger switches to
        per-shard accounting — the bytes ONE device holds — so
        ``capacity_reduction`` stays comparable to the per-GPU Table 4.3
        simulator.  ``bind_mesh(None)`` returns to single-device mode."""
        self.mesh = mesh
        if mesh is None:
            self.model_shards = 1
        else:
            from repro.runtime.sharding import mesh_axis_sizes
            self.model_shards = int(mesh_axis_sizes(mesh).get("model", 1))
        self.ledger.shards = self.model_shards
        return self

    def sharding_for(self, tensor_class: str, spec, *, key: str | None = None):
        """The NamedSharding a tensor-class leaf should carry on the
        bound mesh: the resolved partition spec + the class's policy tier
        (``key`` disambiguates per-leaf tiers, e.g. OffloadBetweenSteps
        pool vs bookkeeping leaves)."""
        if self.mesh is None:
            raise ValueError("no mesh bound; call bind_mesh first")
        from repro.runtime.sharding import resolve_spec
        policy = self.policies.get(tensor_class, PinLocal())
        resolved = resolve_spec(spec, self.mesh)
        if isinstance(policy, OffloadBetweenSteps):
            return policy.sharding(self.mesh, resolved, key=key)
        return policy.sharding(self.mesh, resolved)

    @staticmethod
    def placed_bytes(tree: Any) -> int:
        """Bytes ONE device holds of a placed pytree (exact via each
        leaf's shard shape; total bytes for sharding-less leaves)."""
        total = 0
        for x in jax.tree.leaves(tree):
            sharding = getattr(x, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                n = 1
                for d in sharding.shard_shape(x.shape):
                    n *= d
                total += n * x.dtype.itemsize
            else:
                total += x.size * x.dtype.itemsize
        return total

    def place_params(self, params: Any, spec_tree: Any) -> Any:
        """Mesh-aware whole-model placement: logical specs are resolved
        by ``runtime.sharding.named_shardings`` (pageable groups land in
        the remote tier when paging is enabled), and the ledger records
        the per-shard residency of both tiers."""
        from repro.runtime.sharding import PAGEABLE_GROUPS, named_shardings
        if self.mesh is None:
            raise ValueError("no mesh bound; call bind_mesh first")
        shardings = named_shardings(spec_tree, self.mesh,
                                    pageable_remote=self.config.enabled)
        placed = jax.tree.map(jax.device_put, params, shardings)
        local = remote = 0
        for path, x in jax.tree_util.tree_leaves_with_path(placed):
            nb = self.placed_bytes(x)
            if (self.config.enabled and path
                    and getattr(path[0], "key", None) in PAGEABLE_GROUPS):
                remote += nb
            else:
                local += nb
        if remote:
            self.ledger.record(tiers.REMOTE, "params", remote)
            self.ledger.record_capacity(tiers.REMOTE, "params", remote)
        self.ledger.record(tiers.LOCAL, "params", local)
        # placements provision exactly what they hold: registering the
        # bytes as capacity too keeps the per-tier ``hwm_bytes <=
        # capacity_bytes`` invariant meaningful (a placed class that only
        # recorded residency used to push hwm past the provisioned total)
        self.ledger.record_capacity(tiers.LOCAL, "params", local)
        return placed

    @property
    def expert_policy(self) -> TopKExpertPrefetch | None:
        return self.policies.get("expert_weights")

    def weights_fetch_filter(self) -> Callable[[str], bool] | None:
        """Leaf filter for the layer scans: expert banks stay at rest
        when an expert policy owns them (their rows are gathered on
        demand), everything else rides the prefetch window."""
        ep = self.expert_policy
        if ep is None:
            return None
        return lambda path: not ep.matches(path)

    # ----- placement --------------------------------------------------------
    def place(self, tensor_class: str, tree: Any,
              access_stats: dict | None = None) -> Any:
        """Place a whole tensor class in the tier its policy picks —
        the home tier, or a colder one when ``access_stats`` justify it
        (``pick_tier``) — recording residency, provisioned capacity and
        the placement's tier-edge transfer charge.

        Degradation contract (same as :meth:`place_kv_pool`): an eager
        placement that exhausts its retry budget falls back to LOCAL
        residency and records the reason in ``degraded[tensor_class]``
        — a failed placement is never silent."""
        policy = self.policies.get(tensor_class, PinLocal())
        # pick_tier is optional on ad-hoc policies — home tier then
        tier = (policy.pick_tier(access_stats)
                if hasattr(policy, "pick_tier") else policy.tier)
        nb = tree_bytes(tree)
        try:
            placed = (policy.place(tree) if tier == policy.tier
                      else tiers.eager_to_tier(
                          tree, tier, what=f"place_{tensor_class}"))
        except tiers.TierTransferError as e:
            self.degraded[tensor_class] = (
                f"{tier} placement -> local residency ({e})")
            tier = tiers.LOCAL
            placed = tree
        self.ledger.record(tier, tensor_class, nb)
        self.ledger.record_capacity(tier, tensor_class, nb)
        if tier != tiers.LOCAL:
            self.ledger.charge_transfer(tiers.LOCAL, tier, nb)
        return placed

    def place_layer_weights(self, stacked: Any) -> Any:
        """Place stacked per-layer params: expert-bank leaves go to the
        expert policy's tier, the rest to the layer-weights policy's.
        Records both residencies plus the local prefetch window, and
        charges the placement transfers.  An unrecoverable tier fault
        degrades to local residency (paging disabled, reason recorded
        in ``degraded["layer_weights"]``) — same contract as
        :meth:`place_kv_pool`."""
        wp = self.policies["layer_weights"]
        ep = self.expert_policy

        def put(path, x):
            p = jax.tree_util.keystr(path)
            if ep.matches(p):
                return tiers.host_put(x)
            return x if isinstance(wp, PinLocal) else tiers.host_put(x)

        try:
            placed = (wp.place(stacked) if ep is None
                      else jax.tree_util.tree_map_with_path(put, stacked))
        except tiers.TierTransferError as e:
            self.degraded["layer_weights"] = (
                f"remote paging -> local residency ({e})")
            wp = PinLocal()
            self.policies["layer_weights"] = wp
            self.config = dataclasses.replace(self.config, enabled=False)
            placed = stacked
            ep = None
        if ep is None:
            expert_bytes = 0
        else:
            expert_bytes = sum(
                x.size * x.dtype.itemsize
                for p, x in jax.tree_util.tree_leaves_with_path(stacked)
                if ep.matches(jax.tree_util.keystr(p)))
            self.ledger.record(ep.tier, ep.tensor_class, expert_bytes)
            self.ledger.record_capacity(ep.tier, ep.tensor_class,
                                        expert_bytes)
            if ep.tier != tiers.LOCAL:
                self.ledger.charge_transfer(tiers.LOCAL, ep.tier,
                                            expert_bytes)
        total = tree_bytes(stacked)
        if wp.tier == tiers.REMOTE:
            self.ledger.charge_transfer(tiers.LOCAL, tiers.REMOTE,
                                        total - expert_bytes)
            self.ledger.record(tiers.REMOTE, "layer_weights",
                               total - expert_bytes)
            self.ledger.record_capacity(tiers.REMOTE, "layer_weights",
                                        total - expert_bytes)
            # the prefetch window covers only leaves the scan fetches —
            # expert banks stay at rest (rows gather on demand instead)
            num_layers = jax.tree.leaves(stacked)[0].shape[0]
            per_layer = (total - expert_bytes) // max(num_layers, 1)
            window = int(paged_window_bytes(per_layer, self.config.lookahead))
            self.ledger.record(tiers.LOCAL, "layer_weights_window", window)
            self.ledger.record_capacity(tiers.LOCAL, "layer_weights_window",
                                        window)
        else:
            self.ledger.record(tiers.LOCAL, "layer_weights",
                               total - expert_bytes)
            self.ledger.record_capacity(tiers.LOCAL, "layer_weights",
                                        total - expert_bytes)
        return placed

    def place_kv_pool(self, cache: Any, specs: Any = None) -> Any:
        """Residency for the serving KV cache (dense slab or block
        pool): parked in the remote tier under ``offload_kv`` (only one
        layer's slice local at a time), device-resident otherwise.

        With a bound mesh and a spec tree (``model.cache_specs()`` /
        ``model.paged_cache_specs()``) the cache is sharded — KV heads
        over the ``"model"`` axis — and capacity is recorded per shard.
        """
        policy = self.policies["kv_pool"]
        if self.mesh is not None and specs is not None:
            placed = {k: jax.device_put(
                          v, self.sharding_for("kv_pool", specs[k], key=k))
                      for k, v in cache.items()}
            self.ledger.record_capacity(policy.tier, "kv_pool",
                                        self.placed_bytes(placed))
            return placed
        try:
            placed = policy.place(cache)
        except tiers.TierTransferError as e:
            # documented degradation: when the remote tier cannot take
            # the KV pool (unrecoverable transfer fault), fall back to
            # local residency instead of failing the server — capacity
            # reduction is lost, correctness is not.  The offload
            # transform is disabled too so decode stops round-tripping
            # slices through the faulty tier.
            self.degraded["kv_pool"] = (
                f"remote offload -> local residency ({e})")
            policy = PinLocal()
            self.policies["kv_pool"] = policy
            self.config = dataclasses.replace(self.config, offload_kv=False)
            placed = policy.place(cache)
        # capacity, not residency: a pool slab is provisioned at full
        # size while only live pages count as in-use (no double count)
        self.ledger.record_capacity(policy.tier, "kv_pool",
                                    tree_bytes(cache))
        if policy.tier != tiers.LOCAL:
            self.ledger.charge_transfer(tiers.LOCAL, policy.tier,
                                        tree_bytes(cache))
        return placed

    def kv_in(self, cache: Any) -> Any:
        """Page a remote-tier KV cache (``offload_kv``) into device memory
        at the start of a traced step: XLA computes no update in host
        memory.  The owner rests it back with :meth:`rest_kv_pool`."""
        return tiers.page_in(cache) if self.config.offload_kv else cache

    def rest_kv_pool(self, cache: Any) -> Any:
        """Return a dispatch's KV cache to its policy's tier at rest: the
        remote tier under ``offload_kv`` (a jitted step hands its cache
        back in device memory), unchanged when the pool is local."""
        policy = self.policies["kv_pool"]
        if not isinstance(policy, OffloadBetweenSteps):
            return cache
        kind = tiers.resolved_kind(policy.tier)
        return {k: (jax.device_put(v, v.sharding.with_memory_kind(kind))
                    if k in policy.pool_keys else v)
                for k, v in cache.items()}

    # ----- block pool -------------------------------------------------------
    def block_pool(self, num_pages: int, page_size: int,
                   **kwargs) -> BlockPoolResidency:
        """A ledger-connected block-pool residency (see
        :class:`BlockPoolResidency`); home tier follows the kv_pool
        policy."""
        kwargs.setdefault("tier", self.policies["kv_pool"].tier)
        kwargs.setdefault("shard_factor", self.model_shards)
        return BlockPoolResidency(num_pages, page_size,
                                  ledger=self.ledger, **kwargs)

    def staging_swapper(self, *, tensor_class: str = "kv_handoff",
                        **kwargs):
        """A ledger-connected :class:`repro.memory.swap.PageSwapper`
        whose stash lines post under ``tensor_class`` (default
        ``"kv_handoff"`` — the prefill->decode staging buffer in the
        remote tier), keeping engine-handoff bytes separate from the
        preemption swapper's ``"kv_swap"`` lines.  The engine boundary
        runs entirely through this staging contract, so a later
        multi-host deployment only has to re-point the transfer
        functions at a real remote peer."""
        from repro.memory.swap import PageSwapper
        return PageSwapper(ledger=self.ledger, tensor_class=tensor_class,
                           **kwargs)

    # ----- execution --------------------------------------------------------
    def layer_scan(self, body, carry, stacked_weights, xs=None, **kw):
        kw.setdefault("fetch_filter", self.weights_fetch_filter())
        return paged_scan(body, carry, stacked_weights, xs,
                          config=self.config, **kw)

    def layer_scan_cache(self, body, carry, stacked_weights, cache, **kw):
        kw.setdefault("fetch_filter", self.weights_fetch_filter())
        return paged_scan_cache(body, carry, stacked_weights, cache,
                                config=self.config, **kw)

    def layer_map(self, fn, stacked):
        return paged_map(fn, stacked, config=self.config)

    def donating_jit(self, fn: Callable, *,
                     donate_argnums: tuple[int, ...] = (),
                     **jit_kwargs) -> Callable:
        return donating_jit(fn, donate_argnums=donate_argnums,
                            config=self.config, **jit_kwargs)

    def gather_experts(self, banks: dict, ids: jax.Array) -> dict:
        """Routed-expert row gather: through the expert policy when one
        is planned (remote banks, residency recorded), a plain local
        take otherwise."""
        ep = self.expert_policy
        if ep is not None:
            return ep.gather(banks, ids)
        keys = ("wi", "wg", "wo")
        return {k: jnp.take(banks[k], ids, axis=0) for k in keys}

    # ----- introspection ----------------------------------------------------
    def describe(self) -> dict:
        """Policy matrix (+ any fault-forced degradations), for logs."""
        out = {cls: type(p).__name__ for cls, p in self.policies.items()}
        if self.degraded:
            out["degraded"] = dict(self.degraded)
        return out

    def with_config(self, **overrides) -> "MemoryOrchestrator":
        return MemoryOrchestrator(
            dataclasses.replace(self.config, **overrides),
            self.policies, self.ledger)
