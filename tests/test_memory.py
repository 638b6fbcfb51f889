"""repro.memory subsystem: tier registry scoping/reset, the orchestrator's
policy matrix, accounting parity between the live ledger and the Table 4.3
simulator, and expert-paging residency/churn."""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import memory
from repro.configs import build_model, get_config
from repro.core import simulator as S
from repro.core.graphs import Node
from repro.memory import (MemoryLedger, MemoryOrchestrator, TopKExpertPrefetch,
                          accounting, tiers)
from repro.memory.policies import (DoubleBufferPrefetch, OffloadBetweenSteps,
                                   PagerConfig, PinLocal)

RNG = np.random.RandomState(11)


# ---------------------------------------------------------------------------
# tier registry: per-backend scoping + reset()
# ---------------------------------------------------------------------------

def test_registry_resolves_current_backend():
    reg = tiers.registry()
    assert reg.local.available
    assert reg.remote.available == memory.supports_memory_spaces()
    # CPU and TPU backends expose device / pinned_host / unpinned_host as
    # distinct memories: local is HBM, remote the pinned host pool
    if jax.default_backend() in ("cpu", "tpu"):
        assert reg.local.kind == "device"
        assert reg.remote.kind == "pinned_host"
        assert reg.cold.kind == "unpinned_host"


def test_registry_raises_without_a_host_memory_kind(monkeypatch):
    """A backend with no host memory kind cannot back the remote tier:
    resolution raises instead of leaving the tier unbacked (every
    page_out would otherwise be a silent no-op)."""
    class _Mem:
        kind = "device"

    class _Dev:
        def addressable_memories(self):
            return [_Mem()]

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(RuntimeError, match="pinned_host"):
        tiers.TierRegistry()._resolve("fake")


def test_registry_rescopes_after_backend_change(monkeypatch):
    """The old lru_cache went stale if the backend changed mid-process;
    the registry is keyed per backend and re-resolves after reset()."""
    reg = tiers.TierRegistry()
    real = reg.tiers()                      # resolve the real backend once
    monkeypatch.setattr(reg, "_backend", lambda: "fake-tpu")
    monkeypatch.setattr(
        reg, "_resolve",
        lambda backend: {tiers.LOCAL: tiers.Tier(tiers.LOCAL, "device"),
                         tiers.REMOTE: tiers.Tier(tiers.REMOTE,
                                                  "pinned_host")})
    # a NEW backend resolves fresh even without reset (per-backend key)
    assert reg.remote.kind == "pinned_host"
    assert reg.tiers() is not real
    # reset drops every cached resolution
    reg.reset()
    assert reg._tiers == {}


def test_module_reset_invalidates_process_registry():
    before = tiers.registry().tiers()
    memory.reset()
    after = tiers.registry().tiers()
    assert before is not after              # re-resolved, same content
    assert before == after


# ---------------------------------------------------------------------------
# orchestrator: policy matrix + placement
# ---------------------------------------------------------------------------

def test_plan_policy_matrix():
    base = get_config("qwen2.5-14b").reduced()
    assert isinstance(MemoryOrchestrator.plan(base)
                      .policies["layer_weights"], PinLocal)

    m = MemoryOrchestrator.plan(base.with_pager(enabled=True, lookahead=2))
    assert isinstance(m.policies["layer_weights"], DoubleBufferPrefetch)
    assert m.policies["layer_weights"].lookahead == 2
    assert isinstance(m.policies["kv_pool"], PinLocal)
    assert m.expert_policy is None and m.weights_fetch_filter() is None

    m = MemoryOrchestrator.plan(base.with_pager(enabled=True,
                                                offload_kv=True))
    assert isinstance(m.policies["kv_pool"], OffloadBetweenSteps)

    moe = get_config("granite-moe-3b-a800m").reduced()
    m = MemoryOrchestrator.plan(moe.with_pager(enabled=True,
                                               page_experts=True))
    ep = m.expert_policy
    assert isinstance(ep, TopKExpertPrefetch)
    assert (ep.num_experts, ep.top_k) == (moe.num_experts, moe.top_k)
    flt = m.weights_fetch_filter()
    assert not flt("['moe']['wi']") and flt("['moe']['router']")
    assert flt("['attn']['wq']")
    # page_experts on an expert-free family is a no-op
    assert MemoryOrchestrator.plan(
        base.with_pager(page_experts=True)).expert_policy is None


def test_place_layer_weights_roundtrips_and_accounts():
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              remat=False, dtype=jnp.float32)
    model = build_model(cfg.with_pager(enabled=True))
    params = model.init(jax.random.PRNGKey(0))
    placed = model.mem.place_layer_weights(params["layers"])
    led = model.mem.ledger
    total = accounting.tree_bytes(params["layers"])
    assert led.classes(tiers.REMOTE)["layer_weights"] == total
    assert led.classes(tiers.LOCAL)["layer_weights_window"] == \
        accounting.resident_window_bytes(params["layers"], 1)
    # placement preserves values (CPU: remote == host memory)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(placed)[0]),
        np.asarray(jax.tree.leaves(params["layers"])[0]))


def test_place_kv_pool_follows_policy():
    cache = {"k_pages": jnp.zeros((2, 4, 4, 2, 8)),
             "v_pages": jnp.zeros((2, 4, 4, 2, 8)), "meta": jnp.zeros((3,))}
    m = MemoryOrchestrator(PagerConfig())          # PinLocal: identity
    assert m.place_kv_pool(cache)["k_pages"] is cache["k_pages"]
    assert m.ledger.capacity(tiers.LOCAL) == accounting.tree_bytes(cache)
    assert m.ledger.in_use(tiers.LOCAL) == 0       # capacity != residency

    m = MemoryOrchestrator(
        PagerConfig(enabled=True, offload_kv=True),
        {"kv_pool": OffloadBetweenSteps()})
    placed = m.place_kv_pool(cache)
    assert placed["meta"] is cache["meta"]         # small leaves stay put
    assert m.ledger.capacity(tiers.REMOTE) == accounting.tree_bytes(cache)
    np.testing.assert_array_equal(np.asarray(placed["k_pages"]),
                                  np.asarray(cache["k_pages"]))


# ---------------------------------------------------------------------------
# accounting: ledger semantics + parity with the Table 4.3 simulator
# ---------------------------------------------------------------------------

def test_ledger_residency_and_hwm():
    led = MemoryLedger()
    led.record("local", "a", 100)
    led.record("local", "b", 50)
    assert led.in_use("local") == 150 and led.hwm("local") == 150
    led.record("local", "a", 10)           # residency is state, not a sum
    assert led.in_use("local") == 60 and led.hwm("local") == 150
    led.release("local", "b")
    assert led.in_use("local") == 10
    snap = led.snapshot()
    assert snap["local"]["hwm_bytes"] == 150
    assert snap["local"]["by_class"] == {"a": 10}


def test_window_accounting_matches_simulator_peak():
    """Parity: the live pager's resident-window accounting and the
    discrete-event simulator's peak paged window agree for a stream of
    equal-size pageable layers — both reduce to paged_window_bytes."""
    stacked = {"w": jnp.zeros((6, 32, 16), jnp.float32),
               "b": jnp.zeros((6, 16), jnp.float32)}
    per_layer = accounting.tree_bytes(stacked) // 6
    for lookahead in (1, 2):
        measured = accounting.resident_window_bytes(stacked, lookahead)
        nodes = [Node(f"l{i}", "matmul", flops=1e6, local_bytes=per_layer,
                      pageable_bytes=per_layer) for i in range(6)]
        sys = dataclasses.replace(S.fh4(), lookahead=lookahead)
        sim = S.simulate(nodes, sys)
        assert sim.peak_paged_window_bytes == pytest.approx(measured)
        assert measured == accounting.paged_window_bytes(per_layer,
                                                         lookahead)


def test_peak_local_formula_shared_with_simulator():
    """run_workload's Table 4.3 peak goes through accounting.peak_local_
    bytes: window + pinned + activations, nothing else."""
    nodes = [Node(f"l{i}", "matmul", flops=1e6, local_bytes=1e3,
                  pageable_bytes=2e3) for i in range(4)]
    sim = S.simulate(nodes, S.fh4(), pinned_bytes=7e3, activation_bytes=5e2)
    assert sim.peak_local_bytes == pytest.approx(accounting.peak_local_bytes(
        sim.peak_paged_window_bytes, 7e3, 5e2))
    # and the reduction helper is the shared claim formula
    assert accounting.capacity_reduction(10.0, 144.0) == \
        pytest.approx(1 - 10.0 / 144.0)
    assert accounting.capacity_reduction(10.0, 0.0) == 0.0


def test_demo_model_hwm_matches_table43_prediction():
    """The ledger's measured local high-water mark for the demo model's
    paged weights matches the simulator-side prediction (same equal-layer
    window formula Table 4.3 is built on) within tolerance: stacked
    layers are homogeneous, so measured window == (1+w) * mean layer."""
    cfg = get_config("qwen2.5-14b").reduced(num_layers=4)
    model = build_model(cfg.with_pager(enabled=True, lookahead=1))
    params = model.init(jax.random.PRNGKey(0))
    model.mem.place_layer_weights(params["layers"])
    measured = model.mem.ledger.hwm(tiers.LOCAL)
    per_layer = accounting.tree_bytes(params["layers"]) / cfg.num_layers
    predicted = accounting.paged_window_bytes(per_layer, 1)
    assert measured == pytest.approx(predicted, rel=0.01)


# ---------------------------------------------------------------------------
# expert paging: gather semantics, residency bound, churn
# ---------------------------------------------------------------------------

def _banks(e=8, d=16, f=32, dtype=jnp.float32):
    return {"router": jnp.asarray(RNG.randn(d, e), jnp.float32),
            "wi": jnp.asarray(RNG.randn(e, d, f), dtype),
            "wg": jnp.asarray(RNG.randn(e, d, f), dtype),
            "wo": jnp.asarray(RNG.randn(e, f, d), dtype)}


def test_expert_gather_rows_and_residency_bound():
    banks = _banks()
    led = MemoryLedger()
    ep = TopKExpertPrefetch(num_experts=8, top_k=2, ledger=led)
    placed = ep.place({k: banks[k] for k in ep.bank_keys})
    assert led.classes(tiers.REMOTE)["expert_weights"] == \
        accounting.tree_bytes({k: banks[k] for k in ep.bank_keys})
    ids = jnp.asarray([3, 5], jnp.int32)           # one token, top-2
    rows = ep.gather(placed, ids)
    for k in ep.bank_keys:
        np.testing.assert_array_equal(np.asarray(rows[k]),
                                      np.asarray(banks[k][np.asarray(ids)]))
    bank_bytes = accounting.tree_bytes(
        {k: banks[k] for k in ep.bank_keys})
    resident = led.classes(tiers.LOCAL)["expert_weights"]
    assert resident == ep.resident_bytes(banks, 2)
    assert resident <= (ep.top_k + 1) / ep.num_experts * bank_bytes


def test_expert_residency_churn():
    """Random routing churn: recorded residency always respects the
    (rows + 1)/E bound and caps at the full bank + staging."""
    banks = _banks()
    led = MemoryLedger()
    ep = TopKExpertPrefetch(num_experts=8, top_k=2, ledger=led)
    bank_bytes = accounting.tree_bytes({k: banks[k] for k in ep.bank_keys})
    row_bytes = bank_bytes // 8
    rng = random.Random(3)
    for _ in range(50):
        n = rng.randrange(1, 24)                   # tokens*k rows requested
        ids = jnp.asarray([rng.randrange(8) for _ in range(n)], jnp.int32)
        ep.gather(banks, ids)
        resident = led.classes(tiers.LOCAL)["expert_weights"]
        assert resident == (min(n, 8) + 1) * row_bytes
        assert resident <= bank_bytes + row_bytes     # full bank + staging
    assert led.hwm(tiers.LOCAL) <= (8 + 1) * row_bytes


def test_moe_topk_ffn_matches_dense_dispatch():
    """The gathered routed-expert FFN == the dense (E, C, d) dispatch
    (same routing, same keep mask) for decode-shaped inputs."""
    from repro.models.moe import moe_ffn, moe_ffn_topk
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                              dtype=jnp.float32)
    model = build_model(cfg.with_pager(enabled=True, page_experts=True))
    p = model.init_layer(jax.random.PRNGKey(2))["moe"]
    for b, s in ((2, 1), (1, 4)):
        x = jnp.asarray(RNG.randn(b, s, cfg.d_model), jnp.float32) * 0.3
        dense = moe_ffn(p, x, cfg)
        gathered = moe_ffn_topk(p, x, cfg, model.mem)
        np.testing.assert_allclose(np.asarray(gathered), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("temperature,enabled", [(0.0, True), (0.7, True),
                                                 (0.0, False)])
def test_moe_server_expert_paging_matches_dense(temperature, enabled):
    """End-to-end: a served MoE model with expert banks at rest in the
    remote tier emits the same tokens as the dense-bank baseline — with
    the layer-weight pager on AND off (at-rest banks must not stream
    through the disabled path's plain scan either)."""
    from repro.runtime.serve import BatchedServer
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                              remat=False)
    prompt = np.asarray([3, 1, 4, 1], np.int32)

    def serve(model, params):
        server = BatchedServer(model, params, batch_size=1, max_seq=64,
                               block_size=4, temperature=temperature)
        r = server.submit(prompt, max_new_tokens=8)
        server.run_once()
        return r.output, server

    base = build_model(cfg)
    params = base.init(jax.random.PRNGKey(0))
    out_d, _ = serve(base, params)

    emodel = build_model(cfg.with_pager(enabled=enabled, page_experts=True))
    eparams = dict(params)
    eparams["layers"] = emodel.mem.place_layer_weights(params["layers"])
    out_p, server = serve(emodel, eparams)
    assert out_p == out_d
    # resident expert rows bounded by (B*k + 1 staging) rows per bank
    led = emodel.mem.ledger
    per_layer_bank = led.classes(tiers.REMOTE)["expert_weights"] \
        // cfg.num_layers
    bound = (cfg.top_k + 1) / cfg.padded_experts
    assert led.classes(tiers.LOCAL)["expert_weights"] <= \
        bound * per_layer_bank + 1


# ---------------------------------------------------------------------------
# repro.memory is the one import surface (the core.pager shim is gone)
# ---------------------------------------------------------------------------

def test_memory_exports_the_pager_surface():
    with pytest.raises(ImportError):
        from repro.core import pager  # noqa: F401 - removed after one release
    for name in ("paged_scan", "paged_scan_cache", "donating_jit",
                 "tree_bytes", "host_put", "page_in", "page_out",
                 "supports_memory_spaces", "resident_window_bytes",
                 "PagerConfig", "PageSwapper", "FaultPlan",
                 "transfer_with_retry"):
        assert hasattr(memory, name), name
    assert memory.host_put is tiers.host_put
    assert memory.PagerConfig is PagerConfig
