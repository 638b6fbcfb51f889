"""Production meshes.

Functions (not module constants) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_smoke_mesh():
    """Single-device mesh for CPU smoke tests."""
    return jax.make_mesh((1, 1), ("data", "model"), **_axis_kw(2))


def make_host_mesh(data: int = 1, model: int = 1):
    """Arbitrary small mesh from forced host devices (tests)."""
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kw(2))


def make_serving_mesh(model: int = 1, data: int = 1):
    """Tensor-parallel serving mesh: ``model`` shards for weights/KV
    heads, ``data`` replicas for batch sharding.  ``model=1`` is a valid
    degenerate mesh (the sharded serving path on a single device)."""
    return make_host_mesh(data=data, model=model)


def serving_model_shards(max_shards: int, *heads: int) -> int:
    """Largest tensor-parallel degree <= ``max_shards`` (and the local
    device count) that divides every padded head count in ``heads`` —
    how benches and examples pick a mesh for whatever devices exist."""
    limit = max(1, min(max_shards, jax.device_count()))
    for m in range(limit, 0, -1):
        if all(h % m == 0 for h in heads):
            return m
    return 1
