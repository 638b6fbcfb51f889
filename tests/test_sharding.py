"""Sharding resolution layer: registry-routed memory kinds (the CPU
backend regression), logical-spec resolution, and the ambient-mesh
constraint path on a real multi-device host mesh (subprocess, since the
main test process must stay single-device)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_smoke_mesh, serving_model_shards
from repro.memory import tiers
from repro.runtime.sharding import (SEQ_SHARDED_ACTS, ambient_mesh,
                                    maybe_constraint, named_shardings,
                                    resolve_spec)


# ---------------------------------------------------------------------------
# named_shardings: memory kinds come from the TierRegistry, never hardcoded
# ---------------------------------------------------------------------------

def test_named_shardings_resolves_tiers_on_cpu_backend():
    """Memory kinds come from the tier registry, never hardcoded: both
    tiers resolve to backend-real kinds and the shardings actually
    place arrays."""
    mesh = make_smoke_mesh()
    spec_tree = {
        "embed": {"tok": P("model", None)},
        "layers": {"attn": {"wq": P(None, None, "model")}},
    }
    sh = named_shardings(spec_tree, mesh, pageable_remote=True)
    assert sh["layers"]["attn"]["wq"].memory_kind == \
        tiers.resolved_remote_kind()
    assert sh["embed"]["tok"].memory_kind == tiers.resolved_local_kind()
    if jax.default_backend() in ("cpu", "tpu"):
        # both backends expose the chip's kinds: HBM and pinned host
        assert sh["layers"]["attn"]["wq"].memory_kind == "pinned_host"
        assert sh["embed"]["tok"].memory_kind == "device"
    # placement must work, not just construct
    placed = jax.device_put(jnp.zeros((4, 4)), sh["embed"]["tok"])
    assert placed.sharding.memory_kind == tiers.resolved_local_kind()
    placed_r = jax.device_put(jnp.zeros((2, 4, 4)),
                              sh["layers"]["attn"]["wq"])
    assert placed_r.sharding.memory_kind == tiers.resolved_remote_kind()


def test_named_shardings_pageable_only_under_pageable_groups():
    mesh = make_smoke_mesh()
    tree = {"layers": {"w": P(None)}, "ln_f": P(None)}
    sh = named_shardings(tree, mesh, pageable_remote=True)
    assert sh["layers"]["w"].memory_kind == tiers.resolved_remote_kind()
    assert sh["ln_f"].memory_kind == tiers.resolved_local_kind()
    sh_off = named_shardings(tree, mesh, pageable_remote=False)
    assert sh_off["layers"]["w"].memory_kind == tiers.resolved_local_kind()


def test_resolve_spec_drops_missing_axes():
    mesh = make_smoke_mesh()                      # ("data", "model")
    assert resolve_spec(P(("pod", "data"), "model"), mesh) == \
        P(("data",), "model")
    assert resolve_spec(P("pod", None), mesh) == P(None, None)


def test_serving_model_shards_divisibility():
    # expectation derived from the live device count so the test holds
    # on multi-device machines too
    limit = min(8, jax.device_count())
    want = max(m for m in range(1, limit + 1) if 4 % m == 0 and 2 % m == 0)
    assert serving_model_shards(8, 4, 2) == want
    # an explicit cap of 1 wins regardless of devices
    assert serving_model_shards(1, 48, 16) == 1


def test_mesh_compatibility_checks():
    from repro.configs import get_config
    dense = get_config("qwen2.5-14b").reduced()
    dense.assert_mesh_compatible({"model": 1})
    dense.assert_mesh_compatible({"model": 2})
    with pytest.raises(ValueError, match="cannot shard"):
        dense.assert_mesh_compatible({"model": 16})   # 4 heads % 16
    # MoE banks are not covered by the all-gather-TP determinism
    # contract: reject up front instead of serving diverging tokens
    moe = get_config("grok-1").reduced()
    with pytest.raises(ValueError, match="expert-parallel"):
        moe.assert_mesh_compatible({"model": 2})
    moe.assert_mesh_compatible({"model": 1})          # degenerate ok


# ---------------------------------------------------------------------------
# maybe_constraint: strict no-op outside a mesh, real constraint inside
# ---------------------------------------------------------------------------

def test_maybe_constraint_is_identity_without_mesh():
    assert ambient_mesh() is None
    x = jnp.ones((4, 8, 16))
    assert maybe_constraint(x, SEQ_SHARDED_ACTS) is x


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.runtime.sharding import (SEQ_SHARDED_ACTS, activate_mesh,
                                    ambient_mesh, collective_bytes_by_axis,
                                    maybe_constraint, mesh_axis_sizes)

mesh = make_host_mesh(data=2, model=4)
assert mesh_axis_sizes(mesh) == {"data": 2, "model": 4}, mesh_axis_sizes(mesh)

# outside any mesh: strict no-op (identity)
x = jnp.ones((4, 8, 6))
assert ambient_mesh() is None
assert maybe_constraint(x, SEQ_SHARDED_ACTS) is x

with activate_mesh(mesh):
    am = ambient_mesh()
    assert am is not None, "ambient mesh not detected inside the context"
    assert mesh_axis_sizes(am) == {"data": 2, "model": 4}
    # divisible dims: the constraint APPLIES (returns a new value) ...
    y = maybe_constraint(x, SEQ_SHARDED_ACTS)
    assert y is not x, "constraint silently no-op'd on a live mesh"
    # ... and survives into the lowered module as a sharding annotation
    txt = jax.jit(lambda a: maybe_constraint(a, SEQ_SHARDED_ACTS) * 2) \
        .lower(x).as_text()
    assert "sharding" in txt, "no sharding annotation in lowered HLO"
    # non-divisible dims: no-op, not an error and not a bogus constraint
    z = jnp.ones((3, 5, 6))
    assert maybe_constraint(z, SEQ_SHARDED_ACTS) is z

# the all-gather-TP replication constraint is armed ONLY inside
# gather_tp_mode (the serving dispatch context) — a bare mesh context
# (e.g. the dry-run cost model) must leave it a strict no-op
from repro.runtime.sharding import gather_tp_mode, replicate_constraint
with activate_mesh(mesh):
    assert replicate_constraint(x) is x, "fired outside gather_tp_mode"
    with gather_tp_mode():
        assert replicate_constraint(x) is not x, "did not fire when armed"
    assert replicate_constraint(x) is x, "mode leaked past its context"
with gather_tp_mode():
    assert replicate_constraint(x) is x, "fired without an ambient mesh"

# per-axis collective accounting: a contraction over a model-sharded dim
# must show model-axis traffic and no data-axis traffic
a = jax.device_put(jnp.ones((8, 64), jnp.float32), NamedSharding(mesh, P()))
w = jax.device_put(jnp.ones((64, 32), jnp.float32),
                   NamedSharding(mesh, P("model", None)))
hlo = jax.jit(lambda a, w: a @ w).lower(a, w).compile().as_text()
by_axis = collective_bytes_by_axis(hlo, mesh)
assert by_axis.get("model", 0) > 0, by_axis
assert by_axis.get("data", 0) == 0, by_axis

# attribution is by concrete replica_groups device sets, so two axes of
# EQUAL size must still attribute correctly (size-matching would tie)
mesh22 = make_host_mesh(data=2, model=2)
for axis in ("data", "model"):
    wq = jax.device_put(jnp.ones((64, 32), jnp.float32),
                        NamedSharding(mesh22, P(axis, None)))
    aq = jax.device_put(jnp.ones((8, 64), jnp.float32),
                        NamedSharding(mesh22, P()))
    h = jax.jit(lambda a, w: a @ w).lower(aq, wq).compile().as_text()
    ba = collective_bytes_by_axis(h, mesh22)
    other = "model" if axis == "data" else "data"
    assert ba.get(axis, 0) > 0, (axis, ba)
    assert ba.get(other, 0) == 0, (axis, ba)

# a family without serving_param_specs is rejected up front, and the
# failed construction leaves no sharded state behind
import types
from repro.configs import build_model, get_config
from repro.runtime.serve import BatchedServer
cfg = get_config("qwen2.5-14b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
fake = types.SimpleNamespace(cfg=cfg, mem=model.mem)
try:
    BatchedServer(fake, params, batch_size=1, max_seq=32,
                  mesh=make_host_mesh(model=2))
except ValueError as e:
    assert "serving_param_specs" in str(e), e
else:
    raise AssertionError("family without serving_param_specs not rejected")
assert model.mem.mesh is None and model.mem.model_shards == 1, \
    "rejected mesh leaked into the shared orchestrator"
assert model.mem.ledger.shards == 1
print("MESH_OK")
"""


@pytest.mark.slow
def test_maybe_constraint_on_host_mesh():
    """satellite: a real mesh bug can no longer silently no-op the
    constraint — inside a host mesh the constraint must apply (and
    lower), outside it must be an identity."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT, src],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert "MESH_OK" in out.stdout, out.stderr[-3000:]
